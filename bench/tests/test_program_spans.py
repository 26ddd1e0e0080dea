"""The readers of the program's own spans (``yardstick/program.py`` and the
metrics that read it), the reduction that splits idle time by ``rpq.``
spans as well (``yardstick/spantrace.py``), and the level kernel's byte
count."""

from __future__ import annotations

import dataclasses
import os
import tempfile
import time

import numpy as np
import pytest

from conftest import HERE, tiny_cell
from repro import spans
from repro.kernels.frontier import ops
from yardstick import harness, program, spantrace, tracing

FIXTURE = os.path.join(HERE, "data", "packed_small.xplane.pb")
CLOSED = "twin4site.table2_closed"
OPEN = "twin150site.mixed_open"
NEW = ["lane_wait_ms.closed", "lane_wait_ms.open", "plan_estimate_ms.open",
       "answer_sets_ms.closed", "calibrate_ms.closed", "s2_fetch_ms.closed",
       "answer_d2h_mb.closed", "level_kernel_hbm_pct.closed"]


@pytest.fixture(autouse=True)
def quiet_recorder():
    """Importing ``yardstick.program`` turns the recorder on, as a traced
    run needs; each test here starts and ends with it off and empty."""
    spans.disable()
    spans.drain()
    yield
    spans.disable()
    spans.drain()


# -- the reduction --------------------------------------------------------------


def test_fixture_reads_the_same_through_either_reduction():
    old = tracing.reduce(FIXTURE)
    new = spantrace.reduce(FIXTURE)
    bench_only = spantrace.reduce(FIXTURE, prefixes=("bench.",))
    for r in (new, bench_only):
        assert (r.window_s, r.busy_s, r.n_devices, r.op_s) == (
            old.window_s, old.busy_s, old.n_devices, old.op_s)
    assert {k.removeprefix("bench."): v for k, v in bench_only.gaps_by_span.items()} == (
        old.gaps_by_span)
    for name in ("device_idle.closed", "level_kernel_ms.closed"):
        reader = harness.load_reader(name)
        obs = _obs(trace=old, outcomes=[_done()] * 4)
        want = reader.read(obs)
        obs.trace = new
        assert reader.read(obs) == want is not None


def test_gap_inside_an_rpq_span_goes_to_it():
    """A trace recorded here: ``bench.flush`` around ``rpq.flush``, each
    with a pause of its own; a point in each pause goes to the innermost
    span around it."""
    import jax

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            spans.enable()
            with jax.profiler.TraceAnnotation("bench.flush"):
                t_outer = _pause()
                with spans.span("flush"):
                    t_inner = _pause()
        finally:
            jax.profiler.stop_trace()
        host = spantrace.read_events(tracing.find_xplane(d))[1]
    names = {n for _, _, n in host}
    assert {"bench.flush", "rpq.flush"} <= names
    (b0, b1), (r0, r1) = ([(a, b) for a, b, n in host if n == name][0]
                          for name in ("bench.flush", "rpq.flush"))
    assert b0 <= r0 < r1 <= b1
    # the pauses, placed on the trace's clock by their offsets in the spans
    outer = b0 + int(t_outer * 1e9)
    inner = r0 + int(t_inner * 1e9)
    got = spantrace.innermost(host, np.asarray([outer, inner, b1 + 10**9], np.int64))
    assert list(got) == ["bench.flush", "rpq.flush", spantrace.NONE]


def _pause(seconds=0.05) -> float:
    """Sleep, and return the pause's middle as seconds after the call."""
    t0 = time.perf_counter()
    time.sleep(seconds)
    return (time.perf_counter() - t0) / 2


# -- the level kernel's bytes ------------------------------------------------------


def test_level_kernel_bytes_hand_count():
    # (o_row, o_col, f_row, f_col, tile) per grid step, as Stage B sorts them
    steps = np.array([
        (0, 0, 0, 0, 5),
        (0, 0, 0, 1, 6),  # new frontier block, new tile
        (0, 1, 2, 1, 6),  # new output and frontier blocks, same tile
        (0, 1, 2, 1, 7),  # new tile only
        (1, 0, 0, 0, 0),  # cover step: zero tile, frontier (0, 0)
        (1, 1, 0, 0, 0),  # cover step: new output block only
    ], np.int32)
    o_rows, o_cols, f_rows, f_cols, tids = steps.T
    tile = 128 * 4 * 4  # a (128, 4) uint32 tile block
    row = 8 * 128 * 4  # a (q_pad, B) block of 4-byte words
    want = 4 * tile + 4 * row + 4 * row + 7 * 4 * 6
    assert want == 41_128
    assert ops.level_kernel_bytes(tids, f_rows, f_cols, o_rows, o_cols, 8, 128, tile) == want
    assert ops.level_kernel_bytes(tids[:0], f_rows[:0], f_cols[:0], o_rows[:0], o_cols[:0],
                                  8, 128, tile) == 0


def test_stage_b_plan_carries_its_kernel_bytes():
    from repro.core import paa
    from repro.graph.generators import random_labeled_graph

    g = random_labeled_graph(40, 170, 4, seed=3)
    ca = paa.compile_query("(l0|l1)* l2", g)
    staged = ops.stage_graph(g, 8, tile_dtype="uint32")
    plan = ops.build_level_schedule(ca, staged)
    arrays = [np.asarray(a) for a in (plan.tile_ids, plan.f_rows, plan.f_cols,
                                      plan.o_rows, plan.o_cols)]
    tile = 8 * int(staged.tiles.shape[2]) * 4
    assert plan.kernel_bytes == ops.level_kernel_bytes(*arrays, plan.q_pad, 8, tile) > 0


# -- the readers -------------------------------------------------------------------


@dataclasses.dataclass
class _Rec:
    name: str
    t0: float
    t1: float
    flush: int | None = None
    counters: dict = dataclasses.field(default_factory=dict)


def _done():
    from types import SimpleNamespace

    return SimpleNamespace(answers=object(), t_done=1.0, t_due=0.5, t_sent=0.5)


def _obs(trace=None, outcomes=(), records=None):
    obs = harness.Observations(list(outcomes), t_open=0.0, seconds=10.0, setup_s=1.0,
                               trace=trace)
    if records is not None:
        obs.program = records
    return obs


@pytest.mark.parametrize("name", NEW)
def test_reader_without_records_reads_nothing(name, monkeypatch):
    reader = harness.load_reader(name)
    assert reader.read(_obs(records=[])) is None
    # out of the window: ended before the open or after the close
    late = [_Rec(n, 10.5, 11.0, 1, {"answer_bytes": 8, "levels": 2, "kernel_bytes": 8})
            for n in ("aio.lane_wait", "plan.estimate", "flush", "s2.answers",
                      "s2.calibrate", "s2.fetch")]
    assert reader.read(_obs(records=late)) is None
    monkeypatch.setattr(program, "spans", None)  # a program without the recorder
    assert reader.read(_obs()) is None


def test_readers_read_the_window_records(monkeypatch):
    recs = [
        _Rec("aio.lane_wait", 1.0, 1.010), _Rec("aio.lane_wait", 2.0, 2.030),
        _Rec("plan.estimate", 3.0, 3.5),
        _Rec("flush", 4.0, 5.0, flush=1), _Rec("flush", 5.0, 6.0, flush=2),
        _Rec("flush", -2.0, -1.0, flush=3),  # before the window: left out
        _Rec("s2.answers", 4.5, 4.6, flush=1), _Rec("s2.answers", 4.6, 4.9, flush=1),
        _Rec("s2.answers", 5.5, 5.6, flush=2), _Rec("s2.answers", -1.5, -1.2, flush=3),
        _Rec("s2.calibrate", 4.9, 4.95, flush=1),
        _Rec("s2.fetch", 4.2, 4.4, flush=1,
             counters={"answer_bytes": 3_000_000, "levels": 10, "kernel_bytes": 1_000_000}),
        _Rec("s2.fetch", 5.2, 5.3, flush=2,
             counters={"answer_bytes": 1_500_000, "levels": 5, "kernel_bytes": 2_000_000}),
    ]
    obs = _obs(records=recs)

    def read(name):
        return harness.load_reader(name).read(obs)

    assert read("lane_wait_ms.closed") == pytest.approx(20.0)
    assert read("plan_estimate_ms.open") == pytest.approx(500.0)
    assert read("answer_sets_ms.closed") == pytest.approx((400 + 100) / 2)
    assert read("calibrate_ms.closed") == pytest.approx(50 / 2)
    assert read("s2_fetch_ms.closed") == pytest.approx(150.0)
    assert read("answer_d2h_mb.closed") == pytest.approx(4.5 / 3)

    hbm = harness.load_reader("level_kernel_hbm_pct.closed")
    assert hbm.read(obs) is None  # no trace: nothing to divide by
    monkeypatch.setattr(hbm.harness, "peak_of", lambda kind: {"hbm_bytes_per_s": 1e9})
    obs.trace = tracing.Reduced(window_s=10.0, busy_s=1.0, n_devices=1,
                                op_s={"custom-call tpu_custom_call %rpq_packed_level.1": 0.2,
                                      "copy %copy.6": 0.7},
                                gaps_by_span={}, longest_gaps=[])
    # 20 MB moved in 0.2 s of kernel time at 1 GB/s
    assert hbm.read(obs) == pytest.approx(100.0 * 20e6 / (0.2 * 1e9))


@pytest.mark.parametrize("name", [CLOSED, OPEN])
def test_traced_run_reads_the_program_spans(name):
    """A whole traced run on the CPU: the new readers find the program's
    spans (the roofline share needs a TPU kernel in the trace)."""
    spans.enable()  # as importing yardstick.program does in a fresh run
    # the closed cell's first flush takes seconds in the CPU's Pallas
    # interpreter; its window must see one end
    seconds = 10.0 if name == CLOSED else 3.0
    res = harness.run(tiny_cell(name), 2**31 + 29, seconds, True, time.perf_counter(),
                      require_chip=False, log=lambda _: None)
    assert res["correct"]
    mine = {m for m in NEW if m.endswith(name.split(".")[1].split("_")[-1])}
    mine.discard("level_kernel_hbm_pct.closed")
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert mine <= set(got), sorted(mine - set(got))
    assert all(got[m] > 0 for m in mine)
