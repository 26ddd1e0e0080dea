"""The trace reduction on a small trace recorded on a TPU v5e
(``data/packed_small.xplane.pb``, made by ``record_trace.py``: two
flushes of Table-2 q9 on a 4,000-node twin through the packed
executor), and on intervals built by hand."""

from __future__ import annotations

import os

import pytest

from conftest import HERE
from yardstick import harness, tracing

FIXTURE = os.path.join(HERE, "data", "packed_small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tracing.reduce(FIXTURE)


def test_window_and_busy(reduced):
    assert reduced.n_devices == 1
    assert 0 < reduced.busy_s <= reduced.window_s
    assert reduced.window_s < 60


def test_level_kernel_found(reduced):
    reader = harness.load_reader("level_kernel_ms.closed")
    s = reduced.kernel_s(reader.KERNELS)
    assert 0 < s <= reduced.busy_s


def test_gaps_put_down_to_spans(reduced):
    gaps = reduced.gaps_by_span
    assert "flush" in gaps
    idle = reduced.window_s - reduced.busy_s
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6, abs=1e-9)
    bd = reduced.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in bd["device_ops"] + bd["idle_gaps"])


def test_union_merges_overlaps():
    assert tracing._union([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]
