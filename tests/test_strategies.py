"""S1/S2 executors + S1–S4 meters vs the centralized PAA oracle.

The executors are mesh-shape agnostic (sites fold into the local shard),
so correctness runs on the default 1-device mesh here; an 8-device
subprocess test (test_multidevice.py) exercises real collectives.
"""

import numpy as np
import pytest

from repro.core import paa, strategies
from repro.dist import compat
from repro.core import regex as rx
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import distribute, random_overlay
from repro.graph.structure import example_graph, to_device_graph


@pytest.fixture(scope="module")
def setup():
    g = example_graph()
    placement = distribute(g, n_sites=4, replication_rate=0.4, seed=1)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return g, placement, mesh


QUERIES = ["a* b b", "a c (a|b)", "a* b^-1", "(a|b)+", ". ."]


def test_placement_covers_graph(setup):
    g, placement, _ = setup
    union = np.unique(np.concatenate(placement.site_edges))
    assert len(union) == g.n_edges  # every edge somewhere
    assert placement.replication_rate < 1.0
    assert placement.replication.min() >= 1


def test_s1_executor_matches_oracle(setup):
    g, placement, mesh = setup
    dg = to_device_graph(g)
    for q in QUERIES:
        ast = rx.parse(q)
        ca = paa.compile_query(q, g)
        for start in range(g.n_nodes):
            ans, cost = strategies.s1_execute(mesh, placement, ast, ca, start)
            oracle = set(np.nonzero(np.asarray(paa.answers_single_source(ca, dg, start)))[0].tolist())
            assert ans == oracle, (q, start)
            assert cost.strategy == "S1" and cost.unicast_symbols >= 0


def test_s1_cap_overflow_retry(setup):
    g, placement, mesh = setup
    ast = rx.parse("(a|b)+")
    ca = paa.compile_query("(a|b)+", g)
    # tiny cap forces the overflow-retry path
    ans, _ = strategies.s1_execute(mesh, placement, ast, ca, 0, cap=1)
    dg = to_device_graph(g)
    oracle = set(np.nonzero(np.asarray(paa.answers_single_source(ca, dg, 0)))[0].tolist())
    assert ans == oracle


def test_s2_executor_matches_oracle(setup):
    g, placement, mesh = setup
    dg = to_device_graph(g)
    starts = np.arange(g.n_nodes, dtype=np.int32)
    for q in QUERIES:
        ca = paa.compile_query(q, g)
        acc, costs = strategies.s2_execute(mesh, placement, ca, starts, batch_axis="model")
        assert len(costs) == len(starts)
        for s in starts:
            oracle = np.asarray(paa.answers_single_source(ca, dg, int(s)))
            assert (acc[s] == oracle).all(), (q, s)
            assert costs[s].strategy == "S2" and costs[s].broadcast_symbols >= 0


def test_meters_monotonicity(setup):
    g, placement, _ = setup
    index = paa.HostIndex(g)
    for q in QUERIES:
        ast = rx.parse(q)
        ca = paa.compile_query(q, g)
        c1 = strategies.s1_costs(ast, g)
        for start in range(g.n_nodes):
            c2 = strategies.s2_costs(ca, index, start)
            c3 = strategies.s3_costs(ca, index, start)
            # S3 = S2 without cache: never cheaper on either channel
            assert c3.broadcast_symbols >= c2.broadcast_symbols
            assert c3.unicast_symbols >= c2.unicast_symbols
            # S2 retrieves only traversed data: bounded by S1's label superset
            assert c2.unicast_symbols <= c1.unicast_symbols
        c4 = strategies.s4_costs(ast, g, placement)
        assert c4.broadcast_symbols > c1.broadcast_symbols


def test_s2_cost_cap(setup):
    g, _, _ = setup
    index = paa.HostIndex(g)
    ca = paa.compile_query("(a|b)+", g)
    full = strategies.s2_costs(ca, index, 0)
    capped = strategies.s2_costs(ca, index, 0, max_pops=1)
    assert capped.broadcast_symbols <= full.broadcast_symbols


def test_random_graph_cross_check():
    g = random_labeled_graph(40, 160, 4, seed=3)
    placement = distribute(g, n_sites=4, replication_rate=0.3, seed=2)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    dg = to_device_graph(g)
    ca = paa.compile_query("l0 (l1|l2)* l3", g)
    starts = np.arange(0, 40, 5, dtype=np.int32)
    acc, _ = strategies.s2_execute(mesh, placement, ca, starts)
    for i, s in enumerate(starts):
        oracle = np.asarray(paa.answers_single_source(ca, dg, int(s)))
        assert (acc[i] == oracle).all()


def test_overlay_probes():
    net = random_overlay(150, 3.0, seed=0)
    assert net.probe_ping() == 150
    assert net.probe_connection_count() == 2 * net.n_connections
    assert abs(net.mean_degree - 3.0) < 0.1
    g = random_labeled_graph(100, 400, 4)
    placement = distribute(g, 150, replication_rate=0.2, seed=0)
    k_hat = net.probe_replication(placement, n_samples=128)
    assert abs(k_hat - placement.replication_rate) < 0.08


def test_resolve_interpret_is_the_one_decision():
    """None interprets exactly off-TPU; an explicit flag always wins."""
    import jax

    from repro.kernels.frontier.frontier import resolve_interpret

    assert resolve_interpret() is (jax.default_backend() != "tpu")
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


FUSED_BACKENDS = ["frontier_kernel", "frontier_kernel_packed", "frontier_kernel_sharded"]


@pytest.mark.parametrize("backend", FUSED_BACKENDS)
def test_fused_executor_records_resolved_interpret(backend):
    """Every fused executor carries the interpret mode it resolved — the
    default off-TPU, or the caller's explicit choice."""
    import jax

    g = random_labeled_graph(40, 170, 4, seed=3)
    placement = distribute(g, n_sites=2, replication_rate=0.5, seed=0)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    ca = paa.compile_query("(l0|l1)* l2", g)
    kw = dict(backend=backend, graph=g, placement=placement, block_size=8)
    default = strategies.make_s2_step_fn(ca, g.n_nodes, mesh, **kw)
    assert default.interpret is (jax.default_backend() != "tpu")
    compiled = strategies.make_s2_step_fn(ca, g.n_nodes, mesh, interpret=False, **kw)
    assert compiled.interpret is False


@pytest.mark.parametrize("backend", FUSED_BACKENDS)
def test_fused_executor_takes_staged_arrays_as_arguments(backend):
    """The staged tile store reaches the jitted program as an argument:
    no array as large as the tile tensor is a constant of the program
    (a closed-over store would be compiled in, one copy per executor)."""
    import jax

    g = random_labeled_graph(40, 170, 4, seed=3)
    placement = distribute(g, n_sites=2, replication_rate=0.5, seed=0)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    ca = paa.compile_query("(l0|l1)* l2", g)
    fn = strategies.make_s2_step_fn(
        ca, g.n_nodes, mesh, backend=backend, graph=g, placement=placement, block_size=8
    )
    site = np.zeros((1, 1), np.int32)
    starts = np.arange(0, g.n_nodes, 5, dtype=np.int32)
    closed = jax.make_jaxpr(fn)(site, site, site, site.astype(bool), starts)
    (call,) = [e for e in closed.jaxpr.eqns if e.primitive.name in ("pjit", "jit")]
    # the tile store (n_tiles, B, B | W), or the sharded stack of them,
    # is an operand of the call ...
    store = max(
        (v.aval for v in call.invars if v.aval.ndim >= 3 and v.aval.shape[-2] == 8),
        key=lambda a: a.size,
    )
    # ... and nothing of its size was compiled in as a constant
    consts = call.params["jaxpr"].consts
    assert all(np.size(c) < store.size for c in consts), [np.shape(c) for c in consts]
    acc, *_ = fn(site, site, site, site.astype(bool), starts)
    dg = to_device_graph(g)
    for i, s in enumerate(starts):
        assert (np.asarray(acc[i]) == np.asarray(paa.answers_single_source(ca, dg, int(s)))).all()
