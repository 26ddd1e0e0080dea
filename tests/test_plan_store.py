"""Two-stage compilation: GraphPlanStore + two-level ExecutorCache.

The contract under test (ISSUE 5): Stage A (tile packing, staging,
degree vectors — graph-dependent) is built once per (graph-stats epoch,
block size, placement) and shared across automaton signatures and both
fused backends, so a warm executor build for a NEW query signature on a
hot graph performs **zero** ``pack_blocks``/``make_blocked_graph`` calls;
Stage B (grid ordering + scalar-prefetch ids) is rebuilt per signature
and is bit-exact vs the single-stage path.  Also covered: Stage-A
invalidation on the stats-epoch bump (old executors keep working),
executor-cache eviction releasing staged buffers, and an 8-forced-host-
device subprocess run.
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import paa, plans, strategies
from repro.dist import compat
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import Placement
from repro.graph.structure import to_device_graph
from repro.kernels.frontier import ops as fops
from repro.serve.plancache import ExecutorCache

from tests.test_multidevice import CHILD_ENV, SUBPROCESS_TIMEOUT_S

pytestmark = pytest.mark.timeout_s(SUBPROCESS_TIMEOUT_S + 60)

BACKENDS = ("reference", "frontier_kernel", "frontier_kernel_sharded")


def _partition(g, n_sites: int, seed: int = 0) -> Placement:
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, n_sites, g.n_edges)
    site_edges = [np.nonzero(assign == s)[0].astype(np.int64) for s in range(n_sites)]
    return Placement(g, n_sites, site_edges, np.ones(g.n_edges, np.int32))


@pytest.fixture(scope="module")
def setup():
    g = random_labeled_graph(40, 170, 4, seed=7)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return g, to_device_graph(g), _partition(g, 3, seed=1), mesh


# ---------------------------------------------------------------------------
# warm builds pack zero tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["frontier_kernel", "frontier_kernel_sharded"])
def test_warm_build_packs_zero_tiles(setup, backend):
    """Acceptance criterion: building a second executor for a DIFFERENT
    automaton signature on the same graph/placement reuses all Stage-A
    artifacts — zero make_blocked_graph / pack_blocks / staging calls on
    the warm build, only the cheap Stage-B schedule."""
    g, _, placement, mesh = setup
    store = plans.GraphPlanStore()
    cache = ExecutorCache(maxsize=8, plan_store=store)

    def build(query):
        ca = paa.compile_query(query, g)
        return cache.get_or_build(
            ca, g.n_nodes, mesh, backend=backend, graph=g,
            placement=placement, block_size=8, stats_epoch=0,
        )

    build("(l0|l1)* l2")  # cold: pays Stage A once
    fops.reset_build_counters()
    sig_b, _ = build("l0 (l1|l3)+ .^-1")  # new signature, hot graph
    assert fops.BUILD_COUNTERS["make_blocked_graph"] == 0
    assert fops.BUILD_COUNTERS["pack_blocks"] == 0
    assert fops.BUILD_COUNTERS["stage_graph"] == 0
    assert fops.BUILD_COUNTERS["stage_sharded_graph"] == 0
    # Stage B DID run for the new signature
    schedule_kind = (
        "sharded_level_schedule" if backend == "frontier_kernel_sharded"
        else "level_schedule"
    )
    assert fops.BUILD_COUNTERS[schedule_kind] == 1
    assert store.hits > 0
    # and a repeat of the same signature is a pure executor-cache hit
    fops.reset_build_counters()
    sig_b2, _ = build("l0 (l1|l3)+ .^-1")
    assert sig_b2 == sig_b
    assert sum(fops.BUILD_COUNTERS.values()) == 0
    assert cache.hits == 1


def test_both_fused_backends_share_one_store(setup):
    """One store serves both fused backends: after the sharded backend
    staged the placement, the global backend's build packs nothing new
    for the same graph (its Stage-A tensor is keyed separately but the
    store holds both; each is built at most once)."""
    g, _, placement, mesh = setup
    store = plans.GraphPlanStore()
    cache = ExecutorCache(maxsize=8, plan_store=store)
    ca = paa.compile_query("(l0|l1)* l2", g)
    for backend in ("frontier_kernel_sharded", "frontier_kernel"):
        cache.get_or_build(
            ca, g.n_nodes, mesh, backend=backend, graph=g,
            placement=placement, block_size=8, stats_epoch=0,
        )
    misses0 = store.misses
    fops.reset_build_counters()
    ca2 = paa.compile_query("l1 l2*", g)
    for backend in ("frontier_kernel_sharded", "frontier_kernel"):
        cache.get_or_build(
            ca2, g.n_nodes, mesh, backend=backend, graph=g,
            placement=placement, block_size=8, stats_epoch=0,
        )
    assert store.misses == misses0  # warm for BOTH backends
    assert fops.BUILD_COUNTERS["pack_blocks"] == 0


def test_service_warm_build_packs_zero_tiles(setup):
    """End-to-end through QueryService: a new query class (new automaton
    signature) on a hot graph builds its executor with zero tile
    packing, and the flush stats surface the plan-store counters."""
    from repro.core.cost_model import NetworkParams
    from repro.serve.service import QueryService, ServeConfig

    g, _, placement, mesh = setup
    net = NetworkParams(n_peers=50, n_connections=150, replication_rate=0.2)
    svc = QueryService(
        placement, mesh, net,
        config=ServeConfig(
            n_rollouts=30, s2_backend="frontier_kernel_sharded", s2_block_size=8
        ),
    )
    svc.submit("(l0|l1)+", [0, 5], strategy="S2")
    fops.reset_build_counters()
    svc.submit("l0 l2* l3", [1], strategy="S2")  # different signature
    assert fops.BUILD_COUNTERS["pack_blocks"] == 0
    assert fops.BUILD_COUNTERS["make_blocked_graph"] == 0
    assert fops.BUILD_COUNTERS["sharded_level_schedule"] == 1
    s = svc.summary()
    assert s["plan_store"]["hits"] > 0
    assert s["exec_cache"]["builds"] == 2
    assert s["plan_store"]["misses"] > 0


# ---------------------------------------------------------------------------
# bit-exactness vs the single-stage path and the host oracle
# ---------------------------------------------------------------------------


def test_store_routed_answers_bit_exact_all_backends(setup):
    """Answers through the plan-store build path match the pre-refactor
    (storeless) build path and the centralized PAA for all three
    backends, meters included."""
    g, dg, placement, mesh = setup
    starts = np.arange(0, g.n_nodes, 3, dtype=np.int32)
    store = plans.GraphPlanStore()
    for q in ["(l0|l1)* l2 .^-1", "l0 (l1|l2)* l0", ". l1"]:
        ca = paa.compile_query(q, g)
        for backend in BACKENDS:
            acc, costs = strategies.s2_execute(
                mesh, placement, ca, starts, backend=backend, block_size=8,
                plan_store=store, stats_epoch=0,
            )
            acc0, costs0 = strategies.s2_execute(
                mesh, placement, ca, starts, backend=backend, block_size=8,
            )
            assert (acc == acc0).all(), (q, backend)
            for c, c0 in zip(costs, costs0):
                assert c == c0, (q, backend)
            for i, s in enumerate(starts):
                want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
                assert (acc[i] == want).all(), (q, backend, int(s))


def test_staged_schedules_match_single_stage_plans(setup):
    """Stage B over staged artifacts reproduces the one-shot plans array
    for array: the fused grid is a pure function of (graph, automaton)
    regardless of which stage built the tiles."""
    g, _, placement, _ = setup
    ca = paa.compile_query("(l0|l2)+ l1?", g)
    fields = ("step", "src", "dst", "tiles")
    p_one = fops.build_level_plan(ca, fops.make_blocked_graph(g, 8))
    p_two = fops.build_level_schedule(ca, fops.stage_graph(g, 8))
    for f in fields:
        assert (np.asarray(getattr(p_one, f)) == np.asarray(getattr(p_two, f))).all(), f
    site_graphs = [placement.local_graph(s) for s in range(placement.n_sites)]
    s_one = fops.build_sharded_level_plan(ca, site_graphs, 8)
    s_two = fops.build_sharded_level_schedule(ca, fops.stage_sharded_graph(site_graphs, 8))
    assert s_one.bucket_shapes == s_two.bucket_shapes
    assert s_one.n_real_steps == s_two.n_real_steps
    for b_one, b_two in zip(s_one.buckets, s_two.buckets):
        assert b_one.sites == b_two.sites and b_one.slots == b_two.slots
        for f in fields:
            assert (
                np.asarray(getattr(b_one, f)) == np.asarray(getattr(b_two, f))
            ).all(), f


def test_label_degree_vectors_match_symbol_degrees(setup):
    """The Stage-A per-label degree vectors reduce to exactly the
    automaton-dependent group vectors the meters use — wildcard rows
    included."""
    g, _, placement, _ = setup
    site_graphs = [placement.local_graph(s) for s in range(placement.n_sites)]
    v_pad = -(-g.n_nodes // 8) * 8
    ldeg = plans.label_degree_vectors(site_graphs, g.n_labels, v_pad)
    for q in ["(l0|l1)* l2 .^-1", ". l1"]:
        sgroups = strategies.symbol_set_groups(paa.compile_query(q, g))
        deg_slow, pay_slow = strategies._site_symbol_degrees(sgroups, site_graphs, v_pad)
        deg_fast, pay_fast = strategies._site_symbol_degrees(
            sgroups, site_graphs, v_pad, ldeg
        )
        assert (deg_slow == deg_fast).all(), q
        assert (pay_slow == pay_fast).all(), q


# ---------------------------------------------------------------------------
# invalidation + eviction
# ---------------------------------------------------------------------------


def test_stage_a_invalidation_on_epoch_bump(setup):
    """An epoch bump drops exactly the other epochs' Stage-A entries;
    the new epoch restages on demand."""
    g, _, placement, _ = setup
    store = plans.GraphPlanStore()
    store.staged_sharded(placement, 8, epoch=0)
    store.staged_graph(g, 8, epoch=0)
    assert len(store) == 3  # sharded + local_graphs + global
    dropped = store.invalidate_epoch(1)
    assert dropped == 3 and len(store) == 0
    misses0 = store.misses
    store.staged_sharded(placement, 8, epoch=1)
    assert store.misses > misses0  # rebuilt for the new epoch


def test_epoch_bump_preserves_in_flight_executors(setup):
    """refresh_stats invalidates Stage A once, but an executor built for
    the old epoch still runs (its closure owns the staged buffers) and a
    fresh build against the new epoch restages + stays bit-exact."""
    from repro.core.cost_model import NetworkParams
    from repro.serve.service import QueryService, ServeConfig

    g, dg, placement, mesh = setup
    net = NetworkParams(n_peers=50, n_connections=150, replication_rate=0.2)
    svc = QueryService(
        placement, mesh, net,
        config=ServeConfig(
            n_rollouts=30, s2_backend="frontier_kernel_sharded", s2_block_size=8
        ),
    )
    ca = paa.compile_query("(l0|l1)+", g)
    sig, old_fn = svc.exec_cache.get_or_build(
        ca, g.n_nodes, mesh, backend="frontier_kernel_sharded",
        graph=g, placement=placement, block_size=8, stats_epoch=0,
    )
    size0 = len(svc.exec_cache)
    svc.refresh_stats(g)
    assert svc.stats_epoch == 1
    assert len(svc.exec_cache) < size0  # old-epoch executor dropped
    assert all(k[2] == 1 for k in svc.plan_store._lru)  # only new-epoch Stage A
    # the old-epoch step fn still completes (in-flight semantics) …
    acc, _ = strategies.s2_execute(mesh, placement, ca, np.array([0, 4], np.int32),
                                   step_fn=old_fn)
    # … and a new-epoch build restages and matches it bit-exactly
    _, new_fn = svc.exec_cache.get_or_build(
        ca, g.n_nodes, mesh, backend="frontier_kernel_sharded",
        graph=g, placement=placement, block_size=8, stats_epoch=1,
    )
    acc2, _ = strategies.s2_execute(mesh, placement, ca, np.array([0, 4], np.int32),
                                    step_fn=new_fn)
    assert (acc == acc2).all()
    for i, s in enumerate((0, 4)):
        want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
        assert (acc[i] == want).all()


def test_executor_eviction_releases_staged_buffers(setup):
    """Satellite fix: LRU eviction must release the evicted executor's
    jit compilation cache (which holds the baked-in staged tile
    constants), not just drop the Python reference."""
    g, _, placement, mesh = setup
    store = plans.GraphPlanStore()
    cache = ExecutorCache(maxsize=2, plan_store=store)
    queries = ["l0", "l1 l2", "(l0|l3)+"]
    for q in queries:
        ca = paa.compile_query(q, g)
        cache.get_or_build(
            ca, g.n_nodes, mesh, backend="frontier_kernel",
            graph=g, block_size=8, stats_epoch=0,
        )
    assert len(cache) == 2
    assert cache.releases == 1  # the LRU entry was released, not leaked
    # drop_epoch releases everything from other epochs and sweeps the store
    dropped = cache.drop_epoch(keep_epoch=1)
    assert dropped == 2 and len(cache) == 0 and cache.releases == 3
    assert len(store) == 0
    # by-graph index stays consistent
    assert cache.stats()["graphs"] == 0


def test_plan_store_lru_bound(setup):
    """The store itself is bounded: staging more graphs than maxsize
    evicts the least-recently-used Stage-A entry."""
    store = plans.GraphPlanStore(maxsize=2)
    graphs = [random_labeled_graph(16, 40, 3, seed=s) for s in range(3)]
    for g in graphs:
        store.staged_graph(g, 8, epoch=0)
    assert len(store) == 2
    assert store.evictions == 1


# ---------------------------------------------------------------------------
# 8 forced-host devices
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.multidevice
def test_plan_store_on_8_devices():
    """Acceptance criterion: store-routed builds stay bit-exact vs the
    reference backend and the host PAA on 8 real (forced-host) devices,
    with zero tile packing on the warm build."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        from repro.core import paa, plans, strategies
        from repro.dist import compat
        from repro.graph.generators import random_labeled_graph
        from repro.graph.partition import Placement
        from repro.graph.structure import to_device_graph
        from repro.kernels.frontier import ops as fops
        from repro.serve.plancache import ExecutorCache

        assert len(jax.devices()) == 8
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g = random_labeled_graph(40, 170, 4, seed=11)
        dg = to_device_graph(g)
        starts = np.arange(0, 40, 5, dtype=np.int32)
        rng = np.random.default_rng(0)
        assign = rng.integers(0, 4, g.n_edges)
        site_edges = [np.nonzero(assign == s)[0].astype(np.int64) for s in range(4)]
        placement = Placement(g, 4, site_edges, np.ones(g.n_edges, np.int32))

        store = plans.GraphPlanStore()
        cache = ExecutorCache(maxsize=8, plan_store=store)
        for qi, q in enumerate(["(l0|l1)* l2 .^-1", "l0 (l1|l2)* l3"]):
            ca = paa.compile_query(q, g)
            sig, fn = cache.get_or_build(
                ca, g.n_nodes, mesh, backend="frontier_kernel_sharded",
                graph=g, placement=placement, block_size=8, stats_epoch=0)
            if qi == 1:
                assert fops.BUILD_COUNTERS["pack_blocks"] == 0, "warm build packed"
            fops.reset_build_counters()
            acc, costs = strategies.s2_execute(
                mesh, placement, ca, starts, step_fn=fn)
            acc_ref, _ = strategies.s2_execute(mesh, placement, ca, starts)
            assert (acc == acc_ref).all(), q
            for i, s in enumerate(starts):
                want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
                assert (acc[i] == want).all(), (q, int(s))
        print("PLAN_STORE_MULTIDEVICE_OK")
        """
    )
    try:
        res = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=SUBPROCESS_TIMEOUT_S,
            env=CHILD_ENV,
            cwd="/root/repo",
        )
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = (e.stderr or b"").decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
        pytest.fail(
            f"8-device subprocess exceeded {SUBPROCESS_TIMEOUT_S}s\n"
            f"--- child stdout ---\n{out}\n--- child stderr ---\n{err}"
        )
    assert res.returncode == 0 and "PLAN_STORE_MULTIDEVICE_OK" in res.stdout, (
        f"8-device subprocess failed (rc={res.returncode})\n"
        f"--- child stdout ---\n{res.stdout}\n--- child stderr ---\n{res.stderr}"
    )
