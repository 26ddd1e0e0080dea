"""The benchmark's copies of the twin generator, the placement, the
overlay and the seed-path stream give what the program's originals give
now, byte for byte, at a small size."""

from __future__ import annotations

import numpy as np

from repro.graph import generators, partition
from repro.graph import workloads as program_workloads
from yardstick import placement, twin, workloads


def _small():
    return twin.alibaba_like(n_nodes=4000, n_edges=20000, seed=7)


def test_twin_matches_program():
    mine = _small()
    theirs = generators.alibaba_like(n_nodes=4000, n_edges=20000, seed=7)
    assert mine.n_nodes == theirs.n_nodes
    assert mine.labels == theirs.labels
    for k in ("src", "lbl", "dst"):
        a, b = getattr(mine, k), getattr(theirs, k)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    assert twin.TABLE2_QUERIES == generators.TABLE2_QUERIES


def test_placement_and_overlay_match_program():
    g = generators.alibaba_like(n_nodes=4000, n_edges=20000, seed=7)
    for n_sites, rate in ((4, 0.3), (30, 0.2)):
        mine = placement.distribute(g.n_edges, n_sites, replication_rate=rate, seed=11)
        theirs = partition.distribute(g, n_sites, replication_rate=rate, seed=11)
        assert mine.n_sites == theirs.n_sites
        assert mine.replication.tobytes() == theirs.replication.tobytes()
        assert all(a.dtype == b.dtype and a.tobytes() == b.tobytes()
                   for a, b in zip(mine.site_edges, theirs.site_edges))
    src, dst = placement.random_overlay(150, 3.0, seed=3)
    net = partition.random_overlay(150, 3.0, seed=3)
    assert src.tobytes() == net.adj_src.tobytes() and dst.tobytes() == net.adj_dst.tobytes()


def test_stream_matches_program():
    mine_g = _small()
    theirs_g = generators.alibaba_like(n_nodes=4000, n_edges=20000, seed=7)
    fields = dict(n_queries=80, hot_pool=8, hot_fraction=0.8, seed=123)
    mine = workloads.generate(mine_g, workloads.WorkloadConfig(**fields))
    theirs = program_workloads.generate(theirs_g, program_workloads.WorkloadConfig(**fields))
    assert len(mine) == len(theirs) == 80
    for a, b in zip(mine, theirs):
        assert a.query == b.query and a.hot == b.hot
        assert a.starts.dtype == b.starts.dtype and a.starts.tobytes() == b.starts.tobytes()
    assert any(not q.hot for q in mine) and any(q.hot for q in mine)
    # the copy's defaults are the program's
    d_mine, d_theirs = workloads.WorkloadConfig(), program_workloads.WorkloadConfig()
    assert {k: getattr(d_mine, k) for k in fields} == {k: getattr(d_theirs, k) for k in fields}
    assert np.array_equal(
        [getattr(d_mine, f) for f in ("min_len", "max_len", "min_starts", "max_starts")],
        [getattr(d_theirs, f) for f in ("min_len", "max_len", "min_starts", "max_starts")],
    )
