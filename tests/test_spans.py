"""`repro.spans`: the recorder off and on, and the span tree the serving
path emits — for S2 on the packed executor, for S1, and through the
async front end — kept equal to the span table in ``PERF.md``."""

import asyncio
import os
import re
import threading
import tracemalloc

import numpy as np
import pytest

from repro import spans
from repro.core.cost_model import NetworkParams
from repro.core.witness import INF_LEVEL
from repro.dist import compat
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import distribute
from repro.serve.aio import AsyncQueryService
from repro.serve.service import QueryService, ServeConfig

NET = NetworkParams(n_peers=150, n_connections=450, replication_rate=0.2)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def placement():
    g = random_labeled_graph(60, 240, 4, seed=2)
    return distribute(g, n_sites=4, replication_rate=0.3, seed=1)


def make_service(placement, backend="frontier_kernel_packed", **kw):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = ServeConfig(n_rollouts=30, seed=0, s2_backend=backend, s2_block_size=8, **kw)
    return QueryService(placement, mesh, NET, config=cfg)


@pytest.fixture
def recording():
    """Recording on for one test, with nothing left over before or after."""
    spans.drain()
    spans.enable()
    try:
        yield
    finally:
        spans.disable()
        spans.drain()


def by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


def test_off_span_is_the_shared_no_op():
    assert not spans.recording()
    sp = spans.span("flush", request=1, flush=2)
    assert sp is spans.NO_SPAN and not sp
    with sp as inner:
        inner.count("starts", 3)
        assert inner is spans.NO_SPAN
    spans.interval("aio.lane_wait", 0.0, 1.0, request=1, slo="latency")
    assert spans.drain() == []


def test_off_span_allocates_nothing():
    assert not spans.recording()

    def loop(n):
        for _ in range(n):
            with spans.span("s2.call") as sp:
                sp.count("starts", 7)

    loop(10)  # warm any lazy interpreter state first
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        loop(10_000)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename") if s.size_diff > 0)
    assert grown < 4096, grown  # the snapshots' own bookkeeping, not 10k spans


def test_off_flush_leaves_no_record(placement):
    svc = make_service(placement, backend="reference")
    svc.submit("(l0|l1)+", [0, 5], strategy="S2")
    svc.submit("l1 l2", [4], strategy="S1")
    assert spans.drain() == []


def test_nesting_ids_and_counts(recording):
    with spans.span("flush", flush=7) as fl:
        fl.count("requests", 2)
        with spans.span("s2.answers", request=11) as a:
            a.count("starts", 3)
            a.count("starts", 2)
            with spans.span("s2.fetch") as f:
                f.count("answer_bytes", 100)
        with spans.span("s2.calibrate", request=12) as c:
            c.count("observations", 4)
    recs = spans.drain()
    assert spans.drain() == []  # drain clears
    names = by_name(recs)
    fl, a, f, c = (names[n][0] for n in ("flush", "s2.answers", "s2.fetch", "s2.calibrate"))
    assert len({r.id for r in recs}) == 4
    assert fl.parent is None and a.parent == fl.id and c.parent == fl.id and f.parent == a.id
    assert (a.flush, a.request) == (7, 11) and (f.flush, f.request) == (7, 11)
    assert (c.flush, c.request) == (7, 12)
    assert fl.counters == {"requests": 2} and a.counters == {"starts": 5}
    assert f.counters == {"answer_bytes": 100} and c.counters == {"observations": 4}
    assert fl.t0 <= a.t0 <= f.t0 <= f.t1 <= a.t1 <= c.t0 <= c.t1 <= fl.t1
    # children finish first, so the outer span is recorded last
    assert [r.name for r in recs] == ["s2.fetch", "s2.answers", "s2.calibrate", "flush"]


def test_parents_are_per_thread(recording):
    seen = {}

    def worker():
        with spans.span("s2.call") as sp:
            seen["parent"] = sp.parent

    with spans.span("flush"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert seen == {"parent": None}
    assert sorted(r.name for r in spans.drain()) == ["flush", "s2.call"]


def test_interval_is_recorded_as_given(recording):
    spans.interval("aio.lane_wait", 1.5, 2.25, request=3, slo="throughput", fill_flush=1)
    (r,) = spans.drain()
    assert (r.name, r.t0, r.t1, r.request, r.parent) == ("aio.lane_wait", 1.5, 2.25, 3, None)
    assert r.counters == {"slo": "throughput", "fill_flush": 1}


# ---------------------------------------------------------------------------
# the span tree of a served flush
# ---------------------------------------------------------------------------


def test_s2_flush_span_tree(placement, recording):
    svc = make_service(placement)
    t1 = svc.enqueue("(l0|l1)+", [0, 5, 9], strategy="S2")
    t2 = svc.enqueue("l0 l2* l3", [1, 2], strategy="S2")
    svc.flush()
    recs = spans.drain()
    names = by_name(recs)
    assert set(names) == {
        "flush", "plan", "plan.estimate", "plan.compile", "plan.decide",
        "s2.executor", "s2.call", "s2.dispatch", "s2.fetch", "s2.rows",
        "s2.answers", "s2.calibrate", "s2.finish",
    }
    (fl,) = names["flush"]
    ids = {r.id: r for r in recs}
    assert fl.attrs["tickets"] == [t1.id, t2.id]
    assert fl.counters == {"requests": 2, "starts": 5}
    assert all(r.flush == fl.flush for r in recs)
    for r in names["plan"] + names["s2.executor"] + names["s2.call"] + names["s2.rows"]:
        assert r.parent == fl.id
    assert [r.counters["bytes"] for r in names["s2.rows"]] == [
        3 * placement.graph.n_nodes, 2 * placement.graph.n_nodes]  # bool rows, in group order
    for r in names["plan.estimate"] + names["plan.compile"] + names["plan.decide"]:
        assert ids[r.parent].name == "plan" and r.request == ids[r.parent].request
    assert sorted(r.request for r in names["plan"]) == [t1.id, t2.id]
    assert [r.counters["hit"] for r in names["plan"]] == [0, 0]
    assert all(r.counters["rollouts"] == 30 for r in names["plan.estimate"])
    assert sorted(r.counters["built"] for r in names["s2.executor"]) == [1, 1]
    for name in ("s2.dispatch", "s2.fetch"):
        assert all(ids[r.parent].name == "s2.call" for r in names[name])
    # two signatures, one executor call each; the packed executor pads
    # every call to its 256 lanes
    assert sorted(r.counters["starts"] for r in names["s2.call"]) == [2, 3]
    assert all(r.counters["padded"] == 256 for r in names["s2.call"])
    for r in names["s2.fetch"]:
        assert r.counters["answer_bytes"] == 256 * placement.graph.n_nodes  # bool rows
        assert r.counters["levels"] >= 1 and r.counters["kernel_bytes"] > 0
    for name in ("s2.answers", "s2.calibrate", "s2.finish"):
        assert sorted(r.request for r in names[name]) == [t1.id, t2.id]
        assert all(r.parent == fl.id for r in names[name])
    starts = {r.request: r.counters["starts"] for r in names["s2.answers"]}
    assert starts == {t1.id: 3, t2.id: 2}
    assert {r.request: r.counters["observations"] for r in names["s2.calibrate"]} == starts
    assert all(r.counters["forecasts"] == 1 for r in names["s2.calibrate"])  # one per request
    assert t1.result().answers and t2.done


def test_s1_flush_span_tree(placement, recording):
    svc = make_service(placement, backend="reference")
    t1 = svc.enqueue("l1 l2", [4, 0], strategy="S1")
    t2 = svc.enqueue("(l0|l1)+", [3], strategy="S1")
    svc.flush()
    names = by_name(spans.drain())
    assert {"flush", "s1.collect", "s1.gather", "s1.dedup", "s1.answer"} <= set(names)
    assert not any(n.startswith("s2.") for n in names)
    (fl,) = names["flush"]
    (col,) = names["s1.collect"]  # both requests coalesce into one gather
    assert col.parent == fl.id and col.counters.get("retries", 0) == 0
    (gather,) = names["s1.gather"]
    assert gather.parent == col.id and gather.counters["cap"] > 0 and gather.counters["bytes"] > 0
    (dedup,) = names["s1.dedup"]
    assert dedup.parent == col.id and 0 < dedup.counters["edges"] <= placement.graph.n_edges
    answers = {r.request: r for r in names["s1.answer"]}
    assert set(answers) == {t1.id, t2.id}
    assert answers[t1.id].counters["starts"] == 2 and answers[t2.id].parent == fl.id
    assert t1.result().strategy == "S1"


def test_level_counter_matches_the_witness_levels(placement, recording):
    """The packed executor's ``levels`` counter is the number of level-kernel
    calls of its fixpoint: the deepest discovery level plus the last,
    empty level (a discovery level is the call that found the node, plus
    one, the start being 1)."""
    svc = make_service(placement)
    ans = svc.submit("(l0|l1)+ l2", [0, 5, 9], strategy="S2", semantics="witness")
    (fetch,) = by_name(spans.drain())["s2.fetch"]
    finite = ans.levels[ans.levels < INF_LEVEL]
    assert fetch.counters["levels"] == int(finite.max())
    ((_, fn),) = [(k, e.fn) for k, e in svc.exec_cache._lru.items()]
    assert fetch.counters["kernel_bytes"] == fn.kernel_bytes > 0


# ---------------------------------------------------------------------------
# the async front end, and the span table in PERF.md
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(placement):
    """One async window of S2 and S1 requests with recording on: the
    records and the tickets' ids by query."""
    svc = make_service(placement)
    spans.drain()
    spans.enable()
    try:

        async def drive():
            async with AsyncQueryService(svc) as aio:
                return await asyncio.gather(
                    aio.submit("(l0|l1)+", [0, 5], slo="latency", strategy="S2"),
                    aio.submit("(l0|l1)+", [9], slo="latency", strategy="S2"),
                    aio.submit("l1 l2", [4], slo="throughput", strategy="S1"),
                )

        answers = asyncio.run(drive())
    finally:
        spans.disable()
        recs = spans.drain()
    return recs, answers


def test_aio_spans(served):
    recs, answers = served
    names = by_name(recs)
    admits = names["aio.admit"]
    assert len(admits) == 3 and all(r.parent is None for r in admits)
    ids = {r.id: r for r in recs}
    plans = names["plan"]
    assert len(plans) == 3 and all(ids[p.parent].name == "aio.admit" for p in plans)
    assert {p.request for p in plans} == {a.request for a in admits}
    resolved = sum(r.counters["n"] for r in names["aio.resolve"])
    assert resolved == 3 and all(a is not None for a in answers)


def test_lane_waits_end_at_their_flush(served):
    recs, _ = served
    names = by_name(recs)
    flush_of = {t: fl for fl in names["flush"] for t in fl.attrs["tickets"]}
    waits = names["aio.lane_wait"]
    assert {w.request for w in waits} == {a.request for a in names["aio.admit"]}
    admitted = {a.request: a for a in names["aio.admit"]}
    for w in waits:
        fl = flush_of[w.request]
        assert admitted[w.request].t0 <= w.t0 <= w.t1 <= fl.t0
        # nothing but the hand-over to the flush worker lies between
        assert fl.t0 - w.t1 < 5.0
        assert w.counters["slo"] in ("latency", "throughput")
        assert w.counters["fill_flush"] in (0, 1)


def _perf_md_spans() -> set[str]:
    """The span names in the first column of PERF.md's span table (the
    table whose header starts ``| span |``)."""
    text = open(os.path.join(ROOT, "PERF.md")).read()
    table = re.search(r"^\| span \|.*?\n((?:\|.*\n)+)", text, re.M)
    assert table, "PERF.md has no span table"
    names = set()
    for row in table.group(1).splitlines()[1:]:  # skip the --- row
        cell = row.split("|")[1]
        names.update(re.findall(r"`([a-z0-9_.]+)`", cell))
    return names


def test_perf_md_names_exactly_the_emitted_spans(served):
    recs, _ = served
    assert {r.name for r in recs} == _perf_md_spans()


# ---------------------------------------------------------------------------
# the summary blocks are read when a summary is, not pushed per flush
# ---------------------------------------------------------------------------


def test_flush_installs_no_cache_stats(placement, monkeypatch):
    svc = make_service(placement, backend="reference")
    calls = []
    monkeypatch.setattr(svc.metrics, "set_cache_stats", lambda **kw: calls.append(kw))
    svc.submit("(l0|l1)+", [0], strategy="S2")
    svc.submit("l1 l2", [4], strategy="S1")
    assert calls == []
    monkeypatch.undo()
    assert svc.summary()["exec_cache"]["builds"] == 1


def test_async_flush_installs_no_aio_block(placement):
    svc = make_service(placement, backend="reference")
    pushed = []
    real = svc.metrics.set_aio_stats
    svc.metrics.set_aio_stats = lambda block: (pushed.append(block), real(block))

    async def drive():
        async with AsyncQueryService(svc) as aio:
            for i in range(3):  # three flushes, one at a time
                await aio.submit("(l0|l1)+", [i], strategy="S2")
            assert pushed == []
            return aio

    aio = asyncio.run(drive())
    assert len(pushed) == 1  # stop() installs the final block
    block = aio.summary()["aio"]
    assert block["batch_window"]["flushes"] == 3
    assert sum(block["admission"][c]["completed"] for c in block["admission"]) == 3
    assert np.isfinite(block["batch_window"]["fill_ratio"])
