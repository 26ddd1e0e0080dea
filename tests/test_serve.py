"""`repro.serve`: service answers vs the centralized PAA oracle, plan/
executor caching, micro-batching, and cost-feedback recalibration."""

import numpy as np
import pytest

from repro.core import paa, planner, strategies
from repro.core import regex as rx
from repro.core.cost_model import NetworkParams
from repro.dist import compat
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import distribute
from repro.graph.structure import example_graph, to_device_graph
from repro.serve import (
    Calibrator,
    QueryService,
    ServeConfig,
    ServiceOverloaded,
    automaton_signature,
    canonical_key,
    label_class_key,
)
from repro.serve import batcher


NET = NetworkParams(n_peers=150, n_connections=450, replication_rate=0.2)


@pytest.fixture(scope="module")
def setup():
    g = example_graph()
    placement = distribute(g, n_sites=4, replication_rate=0.4, seed=1)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return g, placement, mesh


@pytest.fixture(scope="module")
def service(setup):
    g, placement, mesh = setup
    return QueryService(
        placement, mesh, NET, config=ServeConfig(n_rollouts=100, seed=0)
    )


# ---------------------------------------------------------------------------
# acceptance: a mixed S1/S2 stream matches the centralized oracle
# ---------------------------------------------------------------------------


def test_mixed_stream_matches_oracle(setup, service):
    g, placement, mesh = setup
    dg = to_device_graph(g)
    queries = ["a* b b", "a c (a|b)", "(a|b)+", "a* b^-1", ". ."]
    tickets = []
    for q in queries:
        starts = np.arange(g.n_nodes, dtype=np.int32)
        # planner-decided, plus both forced strategies → a guaranteed mix
        tickets.append((q, service.enqueue(q, starts)))
        tickets.append((q, service.enqueue(q, starts, strategy="S1")))
        tickets.append((q, service.enqueue(q, starts, strategy="S2")))
    service.flush()

    strategies_seen = set()
    for q, t in tickets:
        ans = t.result()
        strategies_seen.add(ans.strategy)
        ca = paa.compile_query(q, g)
        for i, s in enumerate(ans.starts):
            oracle = set(
                np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist()
            )
            assert ans.answers[i] == oracle, (q, ans.strategy, int(s))
    assert strategies_seen == {"S1", "S2"}


def test_submit_returns_answers(setup, service):
    g, _, _ = setup
    dg = to_device_graph(g)
    ans = service.submit("a c (a|b)", [0, 1])
    ca = paa.compile_query("a c (a|b)", g)
    for i, s in enumerate(ans.starts):
        oracle = set(
            np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist()
        )
        assert ans.answers[i] == oracle
    assert ans.latency_s > 0
    assert len(ans.observed) >= 1


# ---------------------------------------------------------------------------
# plan cache: α-equivalence + epoch invalidation
# ---------------------------------------------------------------------------


def test_canonical_key_alpha_equivalence():
    k = canonical_key
    assert k("(a|b)+") == k("(b|a)+") == k("{a,b}+") == k("{b|a}+")
    assert k("a  b") == k("a b")
    assert k("(a|a|b)") == k("{a,b}")
    assert k("{a}") == k("a")
    assert k("(a|b) c") != k("(a|b) d")
    assert k("a^-1") != k("a")
    assert k("a*") != k("a+")


def test_plan_cache_hits_for_equivalent_queries(setup):
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    a1 = svc.submit("(a|b)+", [0])
    assert not a1.plan_cache_hit
    a2 = svc.submit("(b|a)+", [0])  # α-equivalent: same plan entry
    assert a2.plan_cache_hit
    assert a1.answers == a2.answers
    assert a2.plan.query == "(b|a)+"  # the request's own string, not first-seen
    a3 = svc.submit("(a|b)+", [0])
    assert a3.plan_cache_hit


def test_refresh_stats_invalidates_plans(setup):
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    assert not svc.submit("a b", [0]).plan_cache_hit
    assert svc.submit("a b", [0]).plan_cache_hit
    svc.refresh_stats(g)
    assert svc.stats_epoch == 1
    assert not svc.submit("a b", [0]).plan_cache_hit  # new epoch, new entry


# ---------------------------------------------------------------------------
# executor cache + batching
# ---------------------------------------------------------------------------


def test_executor_cache_shared_across_requests(setup):
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    svc.submit("a* b b", [0, 1], strategy="S2")
    builds = svc.exec_cache.builds
    svc.submit("a* b b", [2, 3], strategy="S2")  # same signature: no rebuild
    assert svc.exec_cache.builds == builds
    svc.submit("a* b^-1", [0], strategy="S2")  # different automaton: builds
    assert svc.exec_cache.builds == builds + 1


def test_automaton_signature_discriminates(setup):
    g, _, mesh = setup
    ca1 = paa.compile_query("a b", g)
    ca2 = paa.compile_query("a b", g)
    ca3 = paa.compile_query("a c", g)
    sig = lambda ca: automaton_signature(ca, g.n_nodes, mesh)  # noqa: E731
    assert sig(ca1) == sig(ca2)
    assert sig(ca1) != sig(ca3)


def test_bucket_sizes():
    assert batcher.bucket_size(1) == 1
    assert batcher.bucket_size(3) == 4
    assert batcher.bucket_size(8) == 8
    assert batcher.bucket_size(9) == 16
    assert batcher.bucket_size(3, multiple=4) == 4
    assert batcher.bucket_size(5, multiple=2) == 8
    assert batcher.bucket_size(4000, max_batch=128) == 128
    # non-power-of-two model axes (e.g. a (4, 3) mesh) must terminate
    assert batcher.bucket_size(5, multiple=3) == 6
    assert batcher.bucket_size(7, multiple=3) == 12
    assert batcher.bucket_size(1, multiple=3) == 3
    # the cap stays divisible by the multiple
    assert batcher.bucket_size(200, multiple=3, max_batch=128) == 126


def test_pad_starts():
    out = batcher.pad_starts(np.array([7, 8], np.int32), 4)
    assert out.tolist() == [7, 8, 7, 7]


def test_s2_batched_queries_share_one_call(setup):
    """Two same-signature requests ride one padded batch and both get
    per-start observed costs back."""
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    t1 = svc.enqueue("(a|b)+", [0, 1, 2], strategy="S2")
    t2 = svc.enqueue("(b|a)+", [3, 4], strategy="S2")
    svc.flush()
    a1, a2 = t1.result(), t2.result()
    # 3 + 2 starts pad to one bucket of 8
    assert a1.observed and a2.observed
    assert len(a1.observed) == 3 and len(a2.observed) == 2
    rec = svc.metrics.records[-1]
    assert rec.exec_batch_size == 8


def test_s2_frontier_kernel_backend_serves_oracle_answers(setup):
    """ServeConfig(s2_backend="frontier_kernel"): same-signature queries
    share one fused-grid executor (batch padded to the 8-query row tile)
    and every answer matches the centralized PAA."""
    g, placement, mesh = setup
    dg = to_device_graph(g)
    svc = QueryService(
        placement, mesh, NET,
        config=ServeConfig(
            n_rollouts=50, s2_backend="frontier_kernel", s2_block_size=8
        ),
    )
    t1 = svc.enqueue("(a|b)+", np.arange(g.n_nodes, dtype=np.int32), strategy="S2")
    t2 = svc.enqueue("(b|a)+", [0, 3], strategy="S2")  # same signature: one batch
    svc.flush()
    for t, q in ((t1, "(a|b)+"), (t2, "(b|a)+")):
        ans = t.result()
        ca = paa.compile_query(q, g)
        for i, s in enumerate(ans.starts):
            oracle = set(
                np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist()
            )
            assert ans.answers[i] == oracle, (q, int(s))
    assert svc.exec_cache.builds == 1  # signature-shared fused executor
    # batches pad to the fused kernel's 8-row query stacking
    assert all(r.exec_batch_size % 8 == 0 for r in svc.metrics.records)


class _MaskItem:
    def __init__(self, mask):
        self.label_mask = np.array(mask, bool)


def test_s1_coalescing_groups_by_label_budget():
    a = _MaskItem([1, 0, 0, 0])
    b = _MaskItem([0, 1, 0, 0])
    c = _MaskItem([0, 0, 1, 1])
    groups = batcher.coalesce_s1([a, b, c], max_union_labels=2)
    assert sorted(len(grp) for grp in groups) == [1, 2]
    ab = next(grp for grp in groups if len(grp) == 2)
    assert batcher.union_mask(ab).tolist() == [True, True, False, False]
    # budget of 1: nobody coalesces, oversized items still run
    groups = batcher.coalesce_s1([a, b, c], max_union_labels=1)
    assert [len(grp) for grp in groups] == [1, 1, 1]


def test_s1_ffd_beats_arrival_order_interleaving():
    """The motivating case for size-aware packing: two label families
    interleaved in arrival order.  Greedy closes a group at every switch
    (4 gathers); FFD packs each family into one bin (2 gathers)."""
    fam_a = [_MaskItem([1, 1, 0, 0, 0, 0]), _MaskItem([0, 1, 1, 0, 0, 0])]
    fam_b = [_MaskItem([0, 0, 0, 1, 1, 0]), _MaskItem([0, 0, 0, 0, 1, 1])]
    interleaved = [fam_a[0], fam_b[0], fam_a[1], fam_b[1]]
    assert len(batcher._coalesce_greedy(interleaved, max_union_labels=3)) == 4
    assert len(batcher.coalesce_s1(interleaved, max_union_labels=3)) == 2


def test_s1_packing_never_splits_below_greedy_throughput():
    """Satellite guarantee: coalesce_s1 never produces more gather rounds
    than the old arrival-order greedy, on any stream; groups respect the
    budget (oversized singletons excepted) and partition the items."""
    rng = np.random.default_rng(11)
    for trial in range(60):
        n_labels = int(rng.integers(4, 24))
        budget = int(rng.integers(1, n_labels + 2))
        items = [
            _MaskItem(rng.random(n_labels) < rng.uniform(0.05, 0.6))
            for _ in range(int(rng.integers(1, 14)))
        ]
        groups = batcher.coalesce_s1(items, budget)
        greedy = batcher._coalesce_greedy(items, budget)
        assert len(groups) <= len(greedy), trial
        flat = [it for grp in groups for it in grp]
        assert sorted(map(id, flat)) == sorted(map(id, items)), trial
        for grp in groups:
            popcount = int(batcher.union_mask(grp).sum())
            assert popcount <= budget or len(grp) == 1, trial


def test_s1_cost_weighted_bins_by_d_s1_not_popcount():
    """ROADMAP satellite: with per-label D_s1 weights, the bin size is
    the gather payload.  Two single-label queries on a hot label exceed
    the budget (popcount packing would coalesce them), while four rare
    labels pack into one gather (popcount packing would need two)."""
    # label 0 carries ~all edges; labels 1..4 are rare
    weights = np.array([96.0, 1.0, 1.0, 1.0, 1.0])  # mean = 20
    hot_a = _MaskItem([1, 0, 0, 0, 0])
    hot_b = _MaskItem([1, 1, 0, 0, 0])
    rare = [_MaskItem(np.eye(5, dtype=bool)[i]) for i in range(1, 5)]
    budget = 2  # weighted capacity = 2 × mean = 40 symbols

    weighted = batcher.coalesce_s1([hot_a, hot_b] + rare, budget, weights)
    # each hot query is an oversized singleton; the 4 rare ones share a bin
    assert sorted(len(g) for g in weighted) == [1, 1, 4]
    for grp in weighted:
        assert not (hot_a in grp and hot_b in grp)
    # popcount packing happily coalesces the hot pair (cheap in labels,
    # huge in gather payload) and splits the rare ones across bins
    unweighted = batcher.coalesce_s1([hot_a, hot_b] + rare, budget)
    assert any(hot_a in grp and hot_b in grp for grp in unweighted)
    assert max(len(g) for g in unweighted) < 4


def test_s1_weighted_packing_keeps_greedy_floor_and_budget():
    """The never-worse-than-greedy guarantee and the (weighted) budget
    hold on random streams with skewed label weights."""
    rng = np.random.default_rng(23)
    for trial in range(60):
        n_labels = int(rng.integers(4, 24))
        budget = int(rng.integers(1, n_labels + 2))
        weights = rng.pareto(1.5, n_labels) + 0.1  # heavy-tailed label costs
        items = [
            _MaskItem(rng.random(n_labels) < rng.uniform(0.05, 0.6))
            for _ in range(int(rng.integers(1, 14)))
        ]
        groups = batcher.coalesce_s1(items, budget, weights)
        greedy = batcher._coalesce_greedy(items, budget, weights)
        assert len(groups) <= len(greedy), trial
        flat = [it for grp in groups for it in grp]
        assert sorted(map(id, flat)) == sorted(map(id, items)), trial
        cap = budget * float(weights.mean())
        for grp in groups:
            cost = float(weights[batcher.union_mask(grp)].sum())
            assert cost <= cap + 1e-9 or len(grp) == 1, trial


def test_s1_unweighted_weights_reduce_to_popcount():
    """Uniform weights reproduce the popcount packing exactly (the
    budget rescaling keeps max_union_labels semantics)."""
    rng = np.random.default_rng(7)
    items = [_MaskItem(rng.random(9) < 0.4) for _ in range(10)]
    uniform = np.full(9, 3.0)
    a = batcher.coalesce_s1(items, 4)
    b = batcher.coalesce_s1(items, 4, uniform)
    assert [[id(x) for x in g] for g in a] == [[id(x) for x in g] for g in b]


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------


def test_admission_queue_bound(setup):
    g, placement, mesh = setup
    svc = QueryService(
        placement, mesh, NET, config=ServeConfig(n_rollouts=50, max_pending=2)
    )
    svc.enqueue("a b", [0])
    svc.enqueue("a b", [1])
    with pytest.raises(ServiceOverloaded):
        svc.enqueue("a b", [2])
    svc.flush()
    svc.enqueue("a b", [2])  # drained: admits again
    svc.flush()


def test_malformed_requests_rejected_at_admission(setup):
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    good = svc.enqueue("a b", [0])
    with pytest.raises(ValueError):
        svc.enqueue("a (b", [0])  # unbalanced paren: rejected immediately
    with pytest.raises(ValueError):
        svc.enqueue("a b", [g.n_nodes + 7])  # out-of-range start node
    with pytest.raises(ValueError):
        svc.enqueue("a b", [-1])
    with pytest.raises(ValueError):
        svc.enqueue("a b", [0], strategy="s2")  # typo'd override must not run S1
    assert svc.n_pending == 1  # none of the bad requests entered the queue
    svc.flush()
    assert good.result().answers is not None


def test_one_failed_request_does_not_drop_the_window(setup):
    """A request that fails mid-plan resolves its own ticket with the
    error; everything else in the window still completes."""
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    good = svc.enqueue("a b", [0])
    bad = svc.enqueue("a b", [0])
    svc._queue[1].ast = object()  # sabotage planning for one request
    svc.flush()
    assert good.result().answers is not None
    with pytest.raises(TypeError):
        bad.result()


def test_concurrent_flushes_serialize(setup):
    """Regression (async runtime): flushes from several threads must
    serialize on one drain at a time — interleaved drains used to
    resolve tickets out of two half-consistent queue snapshots.  Every
    ticket resolves exactly once and every record lands."""
    import threading

    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    tickets, errs = [], []
    start = threading.Barrier(4)

    def worker(k):
        mine = [svc.enqueue("a b", [k]) for _ in range(5)]
        tickets.extend(mine)
        start.wait()
        try:
            svc.flush()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert all(t.done for t in tickets)
    assert len(svc.metrics.records) == 20  # each request exactly once
    assert svc.n_pending == 0


def test_reentrant_flush_defers_instead_of_deadlocking(setup):
    """A flush issued from *inside* the executing flush (same thread —
    e.g. a callback submitting a follow-up) returns [] and leaves its
    requests queued for the next drain, rather than deadlocking on the
    flush lock or double-draining."""
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    inner: list = []
    orig = svc._run_s1

    def reentrant_run(reqs):
        svc.enqueue("a b", [1])  # a follow-up admitted mid-flush ...
        inner.append(svc.flush())  # ... must NOT drain from in here
        orig(reqs)

    svc._run_s1 = reentrant_run
    first = svc.enqueue("a b", [0], strategy="S1")
    svc.flush()
    assert inner == [[]]
    assert first.done
    assert svc.n_pending == 1  # the follow-up waits for the next drain
    svc._run_s1 = orig
    svc.flush()
    assert svc.n_pending == 0


def test_unresolved_ticket_raises(setup):
    g, placement, mesh = setup
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=50))
    t = svc.enqueue("a b", [0])
    with pytest.raises(RuntimeError):
        t.result()
    svc.flush()
    t.result()


# ---------------------------------------------------------------------------
# feedback recalibration
# ---------------------------------------------------------------------------


def test_calibrator_converges_to_observed_ratio():
    cal = Calibrator(decay=0.5)
    key = (("a", "b"), False)
    est = planner.PlanEstimates(
        query="a b", q_lbl=2.0, d_s1=100.0,
        q_bc_samples=np.full(32, 10.0), d_s2_samples=np.full(32, 50.0),
        wildcard=False,
    )
    plan = planner.decide_strategy(est, NET)
    obs = strategies.StrategyCost("S1", 2.0, 200.0)  # observed 2× the estimate
    for _ in range(12):
        cal.observe(key, est, plan, obs)
    f = cal.factors(key)
    assert abs(f.d_s1 - 2.0) < 0.01
    assert f.q_bc == 1.0  # S1 observations never touch the S2 channels


def test_calibration_scales_planner_estimates():
    est = planner.PlanEstimates(
        query="a b", q_lbl=2.0, d_s1=100.0,
        q_bc_samples=np.full(32, 10.0), d_s2_samples=np.full(32, 50.0),
        wildcard=False,
    )
    base = planner.decide_strategy(est, NET)
    scaled = planner.decide_strategy(est, NET, d_s1_scale=2.0, q_bc_scale=3.0)
    assert scaled.d_s1_est == pytest.approx(2 * base.d_s1_est)
    assert scaled.q_bc_quantiles[0.9] == pytest.approx(3 * base.q_bc_quantiles[0.9])


def test_calibrator_clamps_pathological_ratios():
    cal = Calibrator(decay=1.0, clamp=(0.2, 5.0))
    key = (("a",), False)
    est = planner.PlanEstimates(
        query="a", q_lbl=1.0, d_s1=1.0,
        q_bc_samples=np.full(8, 1.0), d_s2_samples=np.full(8, 1.0),
        wildcard=False,
    )
    plan = planner.decide_strategy(est, NET)
    cal.observe(key, est, plan, strategies.StrategyCost("S1", 1.0, 1e9))
    assert cal.factors(key).d_s1 == 5.0


def _observe_per_cost(cal, key, est, plan, costs):
    """The per-cost arithmetic of one ``observe`` a cost, forecast and
    ``np.clip`` included: what ``observe_many`` must reproduce exactly."""
    slot = cal._factors.setdefault(key, {})

    def update(channel, target):
        prev = slot.get(channel, 1.0)
        slot[channel] = (1.0 - cal.decay) * prev + cal.decay * float(np.clip(target, *cal.clamp))

    for c in costs:
        cal.n_observations += 1
        if c.strategy == "S1":
            if est.d_s1 > 0 and c.unicast_symbols > 0:
                update("d_s1", c.unicast_symbols / est.d_s1)
            continue
        _, q_bc_raw, d_s2_raw = planner.calibrated_samples(est)
        q_bc_fc = float(np.quantile(q_bc_raw, plan.decision_quantile))
        d_s2_fc = float(np.quantile(d_s2_raw, plan.decision_quantile))
        if q_bc_fc > 0 and c.broadcast_symbols > 0:
            update("q_bc", c.broadcast_symbols / q_bc_fc)
        if d_s2_fc > 0 and c.unicast_symbols > 0:
            update("d_s2", c.unicast_symbols / d_s2_fc)


_Cost = strategies.StrategyCost


@pytest.mark.parametrize("q_bc_samples, costs, forecasts", [
    pytest.param(  # mixed ratios
        np.linspace(1.0, 40.0, 600),
        [_Cost("S2", 3.0, 70.0), _Cost("S2", 41.7, 0.3), _Cost("S2", 12.0, 12.0), _Cost("S2", 0.9, 1e3)],
        1, id="s2-mixed"),
    pytest.param(  # far above and far below the clamp
        np.linspace(1.0, 40.0, 600),
        [_Cost("S2", 1e9, 1e-6), _Cost("S2", 1e-6, 1e9), _Cost("S2", 1e9, 1e9), _Cost("S2", 7.0, 7.0)],
        1, id="s2-clamped"),
    pytest.param(  # a channel skipped when its observed symbols are zero
        np.linspace(1.0, 40.0, 600),
        [_Cost("S2", 0.0, 25.0), _Cost("S2", 19.0, 0.0), _Cost("S2", 0.0, 0.0), _Cost("S2", 5.0, 6.0)],
        1, id="s2-zero-symbols"),
    pytest.param(  # no rollout broadcasts: the unfiltered samples, q_bc skipped
        np.zeros(600),
        [_Cost("S2", 4.0, 30.0), _Cost("S2", 9.0, 2.0)],
        1, id="s2-all-zero-q_bc"),
    pytest.param(
        np.linspace(1.0, 40.0, 600), [_Cost("S1", 2.0, 310.0)], 0, id="s1"),
    pytest.param(np.linspace(1.0, 40.0, 600), [], 0, id="empty"),
])
def test_observe_many_equals_one_observation_per_cost(q_bc_samples, costs, forecasts):
    key = (("a", "b"), False)
    rng = np.random.default_rng(7)
    est = planner.PlanEstimates(
        query="a b", q_lbl=2.0, d_s1=120.0,
        q_bc_samples=q_bc_samples, d_s2_samples=rng.uniform(0.0, 200.0, 600),
        wildcard=False,
    )
    plan = planner.decide_strategy(est, NET)
    got, want = Calibrator(decay=0.3), Calibrator(decay=0.3)
    for cal in (got, want):  # start from factors an earlier request left
        cal._factors[key] = {"d_s1": 1.7, "q_bc": 0.45, "d_s2": 3.1}
        cal.n_observations = 5
    assert got.observe_many(key, est, plan, costs) == forecasts
    _observe_per_cost(want, key, est, plan, costs)
    assert got._factors == want._factors
    assert got.n_observations == want.n_observations == 5 + len(costs)


def test_service_feedback_loop_runs(setup, service):
    """After serving, the calibrator holds factors for the seen classes
    and they reflect observed/forecast (finite, clamped, not all 1)."""
    s = service.calibrator.summary()
    assert s["n_observations"] > 0
    assert s["n_label_classes"] >= 1
    for factors in s["factors"].values():
        for v in factors.values():
            assert 0.2 <= v <= 5.0


def test_feedback_key():
    assert label_class_key(rx.parse("(a|b)+")) == (("a", "b"), False)
    assert label_class_key(rx.parse("a .")) == (("a",), True)


# ---------------------------------------------------------------------------
# metrics + larger randomized stream
# ---------------------------------------------------------------------------


def test_metrics_summary_schema(setup, service):
    s = service.summary()
    for k in (
        "n_queries", "queries_per_sec", "p50_latency_s", "p95_latency_s",
        "plan_cache_hit_rate", "total_broadcast_symbols",
        "total_unicast_symbols", "strategies", "plan_cache", "exec_cache",
        "calibration", "stats_epoch",
    ):
        assert k in s, k
    assert s["n_queries"] == len(service.metrics.records)
    assert set(s["strategies"]) <= {"S1", "S2"}


def test_randomized_stream_oracle():
    g = random_labeled_graph(40, 160, 4, seed=3)
    placement = distribute(g, n_sites=4, replication_rate=0.3, seed=2)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    svc = QueryService(placement, mesh, NET, config=ServeConfig(n_rollouts=60))
    dg = to_device_graph(g)
    rng = np.random.default_rng(0)
    queries = ["l0 (l1|l2)* l3", "l0 l1", "(l2|l3)+", "l1* l0^-1"]
    tickets = []
    for _ in range(3):  # repeated rounds exercise warm plan + executor caches
        for q in queries:
            starts = rng.integers(0, g.n_nodes, size=rng.integers(1, 5))
            tickets.append((q, svc.enqueue(q, starts)))
        svc.flush()
    for q, t in tickets:
        ans = t.result()
        ca = paa.compile_query(q, g)
        for i, s in enumerate(ans.starts):
            oracle = set(
                np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist()
            )
            assert ans.answers[i] == oracle, (q, ans.strategy, int(s))
    assert svc.plan_cache.hit_rate > 0.5
