"""The traffic generator draws each kind of mix a data file can state,
the same from the same seed, and the span hooks time what the readers
declare."""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from yardstick import hooks, reference, traffic, twin

SEED = 2**31 + 99
SECONDS = 20.0


@pytest.fixture(scope="module")
def small():
    g = twin.alibaba_like(n_nodes=4000, n_edges=20000, seed=0)
    return g, reference.Evaluator(g.n_nodes, g.src, g.lbl, g.dst, g.labels)


def _open(**extra):
    mix = {"loop": "open", "arrivals": "poisson", "rate_qps": 3.0, "tenants": 3,
           "slo": {"latency": 0.7, "throughput": 0.3}, "strategy": None, "warm": "hot",
           "queries": {"source": "seed_path", "stream_seed": 0, "hot_pool": 4,
                       "min_starts": 1, "max_starts": 4}}
    mix.update(extra)
    return mix


def _key(r):
    return (r.query, np.asarray(r.starts).tobytes(), r.tenant, r.slo, r.strategy, r.due,
            r.hot, r.semantics)


def test_same_seed_same_traffic(small):
    a = traffic.build(_open(), *small, SEED, SECONDS)
    b = traffic.build(_open(), *small, SEED, SECONDS)
    c = traffic.build(_open(), *small, SEED + 1, SECONDS)
    assert [_key(r) for r in a.schedule] == [_key(r) for r in b.schedule]
    assert [r.query for r in a.schedule] == [r.query for r in c.schedule]  # the stream is fixed
    assert [r.due for r in a.schedule] != [r.due for r in c.schedule]  # its times are not
    assert len(a.schedule) == 60 and {r.query for r in a.prebuild}.isdisjoint(
        {r.query for r in a.warm})


def test_semantics_split(small):
    t = traffic.build(_open(semantics={"pairs": 0.8, "witness": 0.2}), *small, SEED, SECONDS)
    sems = [r.semantics for r in t.schedule]
    assert sems.count("witness") == 12 and sems.count("pairs") == 48
    assert {(r.query, r.semantics) for r in t.schedule} >= {
        (r.query, r.semantics) for r in t.warm + t.prebuild}
    assert all(r.semantics is None for r in traffic.build(_open(), *small, SEED, SECONDS).schedule)


@pytest.mark.parametrize("arrivals", ["poisson", {"kind": "on_off", "on_s": 2.0, "off_s": 3.0}])
def test_schedule_seed_fixes_the_schedule(small, arrivals):
    def sched(run_seed, schedule_seed=7):
        mix = _open(arrivals=arrivals, schedule_seed=schedule_seed,
                    semantics={"pairs": 0.8, "witness": 0.2})
        return traffic.build(mix, *small, run_seed, SECONDS).schedule

    a, b, c = sched(SEED), sched(SEED + 1), sched(SEED, schedule_seed=8)
    fixed = [(r.query, r.due, r.slo, r.semantics) for r in a]
    assert fixed == [(r.query, r.due, r.slo, r.semantics) for r in b]
    assert [r.tenant for r in a] != [r.tenant for r in b]  # the run's seed draws the tenants
    assert sorted(r.tenant for r in a) == sorted(r.tenant for r in b)  # as a shuffled round robin
    assert fixed != [(r.query, r.due, r.slo, r.semantics) for r in c]
    assert len(a) == 60


def test_on_off_arrivals_fall_in_bursts(small):
    mix = _open(arrivals={"kind": "on_off", "on_s": 2.0, "off_s": 3.0})
    due = np.array([r.due for r in traffic.build(mix, *small, SEED, SECONDS).schedule])
    assert len(due) == 60 and np.all(np.diff(due) >= 0)
    assert np.all(due % 5.0 < 2.0) and due.max() < SECONDS


def test_zipf_tenants(small):
    t = traffic.build(_open(tenants={"n": 4, "zipf": 1.1}), *small, SEED, SECONDS)
    counts = [sum(r.tenant == f"tenant-{k}" for r in t.schedule) for k in range(4)]
    assert sum(counts) == 60 and counts[0] > counts[3]


def test_list_source_closed_and_open(small):
    g, ref = small
    queries = [twin.TABLE2_QUERIES["q1"], twin.TABLE2_QUERIES["q6"]]
    closed = {"loop": "closed", "clients": 2, "tenants": 2, "slo": {"throughput": 1.0},
              "strategy": "S2", "warm": "all", "queries": {"source": "list", "queries": queries}}
    t = traffic.build(closed, g, ref, SEED, SECONDS)
    assert [r.query for r in t.warm] == queries
    firsts = list(itertools.islice(t.clients[1], 4))
    assert sorted(r.query for r in firsts[:2]) == sorted(queries)
    assert all(r.tenant == "tenant-1" and np.array_equal(r.starts, ref.valid_starts(r.query))
               for r in firsts)
    opened = dict(closed, loop="open", rate_qps=1.0, arrivals="poisson")
    assert len(traffic.build(opened, g, ref, SEED, SECONDS).schedule) == 20


def test_seed_path_closed_loop(small):
    mix = _open(loop="closed", clients=3)
    mix["queries"]["n_queries"] = 30
    t = traffic.build(mix, *small, SEED, SECONDS)
    stream = traffic._stream(mix["queries"], small[0], 30)
    got = [r.query for r in itertools.islice(t.clients[2], 12)]
    assert got == [q for q, _, _ in stream[2::3]] + [stream[2][0], stream[5][0]]


class _Counter:
    def __init__(self):
        self.misses = 0

    def stats(self):
        return {"misses": self.misses}

    def work(self, miss):
        self.misses += miss
        return miss


def test_hooks_time_declared_calls_and_restore():
    svc = _Counter()
    readers = [type("R", (), {"SPANS": [{"name": "work", "on": "service", "call": "work",
                                         "count": {"m": "stats().misses"}}]})] * 2
    spans = hooks.Spans()
    hooks.install(spans, svc, hooks.declared(readers))
    assert svc.work(1) == 1 and svc.work(0) == 0
    spans.restore()
    svc.work(1)
    assert [info for _, _, info in spans.records["work"]] == [{"m": 1}, {"m": 0}]
    assert "work" not in vars(svc)
    other = type("R", (), {"SPANS": [{"name": "work", "on": "service", "call": "other"}]})
    with pytest.raises(ValueError):
        hooks.declared(readers + [other])
