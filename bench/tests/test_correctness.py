"""The comparison that decides ``correct``: a sound run passes it, and the
control and each fault the cells can have fail it.

Each test drives a whole run of a cell (set-up, window, drain,
comparison) at a small size on the CPU, past the harness's look for a
chip, with the program's timed path broken underneath where a fault
asks for it.  The control is the configuration's own: the program with
its fixpoint cut after a few levels.
"""

from __future__ import annotations

import itertools
import time

import numpy as np
import pytest

from conftest import tiny_cell
from repro.core import strategies
from repro.kernels.frontier import frontier
from yardstick import harness

CLOSED = "twin4site.table2_closed"
OPEN = "twin150site.mixed_open"
SECONDS = 3.0


def _run(cell, control=False, seed=2**31 + 17, trace=False):
    return harness.run(cell, seed, SECONDS, trace, time.perf_counter(),
                       require_chip=False, control=control, log=lambda _: None)


def _s2_rows(fn):
    """Wrap ``strategies.s2_execute`` so that ``fn`` edits the answer rows
    (padded batch x nodes) where the executor produces them."""
    orig = strategies.s2_execute

    def broken(*args, **kwargs):
        out = orig(*args, **kwargs)
        acc = np.array(out[0])
        fn(acc)
        return (acc,) + tuple(out[1:])

    return broken


def test_sound_closed_run_is_correct():
    res = _run(tiny_cell(CLOSED))
    assert res["correct"] and res["compared"]["wrong_requests"]["value"] == 0
    assert list(res)[-1] == "compared"


def test_sound_open_run_is_correct():
    res = _run(tiny_cell(OPEN))
    assert res["correct"] and res["attempted"] > 0


def test_traced_open_run_reads_the_spans_its_readers_declare():
    res = _run(tiny_cell(OPEN), trace=True)
    assert res["correct"]
    assert {"lane_fill.open", "plan_ms.open", "flush_ms.open", "s2_exec_ms.open"} <= set(res["metrics"])
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("name", [CLOSED, OPEN])
def test_control_is_not_correct(name):
    res = _run(tiny_cell(name), control=True)
    assert not res["correct"]
    assert res["compared"]["wrong_requests"]["value"] > 0


def _flip_one(acc):
    acc[0, (np.argmax(acc[0]) + 1) % acc.shape[1]] ^= True


def _drop_upper_half(acc):
    acc[acc.shape[0] // 2:] = False


def _unchanged_step(orig):
    def step(*args, **kwargs):
        return orig(*args, **kwargs) & 0  # a level that reaches nothing new

    return step


def _sites_left_out(orig):
    def gather(mesh, site_arrays, label_mask, cap, site_axes=("data",)):
        src, lbl, dst, valid, overflow = orig(mesh, site_arrays, label_mask, cap, site_axes)
        valid = valid.copy()
        valid[valid.shape[0] // 2:] = False  # half the sites never answer
        return src, lbl, dst, valid, overflow

    return gather


def test_refused_requests_are_not_correct(monkeypatch):
    """A request the front end refuses in the window is an answer that
    never comes: shedding load cannot read as a faster system."""
    from repro.serve import aio

    window = harness.serve

    async def shedding(*args, **kwargs):
        admit = aio.AsyncQueryService.submit
        calls = itertools.count()

        async def submit(self, *a, **kw):
            if next(calls) % 2:
                raise aio.AdmissionRejected("queue_full", 0.01)
            return await admit(self, *a, **kw)

        monkeypatch.setattr(aio.AsyncQueryService, "submit", submit)
        return await window(*args, **kwargs)

    monkeypatch.setattr(harness, "serve", shedding)
    res = _run(tiny_cell(CLOSED))
    assert not res["correct"] and res["failed"] > 0
    assert res["compared"]["unanswered_requests"]["value"] == res["failed"]
    assert res["compared"]["wrong_requests"]["value"] == 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch", "step_unchanged", "exchange_left_out"])
def test_fault_is_not_correct(monkeypatch, fault):
    cell = tiny_cell(CLOSED)
    if fault == "answer_altered":
        monkeypatch.setattr(strategies, "s2_execute", _s2_rows(_flip_one))
    elif fault == "half_batch":
        monkeypatch.setattr(strategies, "s2_execute", _s2_rows(_drop_upper_half))
    elif fault == "step_unchanged":
        monkeypatch.setattr(frontier, "packed_level_blocks", _unchanged_step(frontier.packed_level_blocks))
    else:
        # the sites' answers to S1's broadcast are the exchange of this
        # system: force S1 and lose half the sites' replies
        cell.mix["strategy"] = "S1"
        monkeypatch.setattr(strategies, "s1_gather", _sites_left_out(strategies.s1_gather))
    res = _run(cell)
    assert not res["correct"], fault
