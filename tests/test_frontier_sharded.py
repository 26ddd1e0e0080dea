"""Site-sharded fused frontier backend: bit-exact oracle match vs the
global ``frontier_kernel`` backend and the reference PAA on 1 simulated
device, per-site §4.2 cost meters summing to the host meter, the
shape-bucketed plan invariants (power-of-two multi-member classes,
singleton natural shapes, in-kernel-skippable padding tails), and an
8-device subprocess run (reusing the ``test_multidevice`` harness
pattern)."""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import paa, strategies
from repro.dist import compat
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import Placement, distribute
from repro.graph.structure import to_device_graph
from repro.kernels.frontier.frontier import unpack_schedule
from repro.kernels.frontier.ops import build_sharded_level_plan

from tests.test_multidevice import CHILD_ENV, SUBPROCESS_TIMEOUT_S

pytestmark = pytest.mark.timeout_s(SUBPROCESS_TIMEOUT_S + 60)


def _partition(g, n_sites: int, seed: int = 0) -> Placement:
    """A true disjoint partition (K=1): every edge lives on exactly one
    site, so per-site response totals sum to the host meter exactly."""
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, n_sites, g.n_edges)
    site_edges = [np.nonzero(assign == s)[0].astype(np.int64) for s in range(n_sites)]
    return Placement(g, n_sites, site_edges, np.ones(g.n_edges, np.int32))


@pytest.fixture(scope="module")
def setup():
    g = random_labeled_graph(40, 170, 4, seed=3)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return g, to_device_graph(g), paa.HostIndex(g), mesh


QUERIES = ["(l0|l1)* l2 .^-1", "l0 (l1|l2)* l0", ". l1", "(l0|l2)+ l1?"]


def test_sharded_matches_global_backend_and_oracle(setup):
    """backend="frontier_kernel_sharded" on a 3-site partition is
    bit-exact vs the global fused backend and the reference PAA —
    wildcard, inverse, and optional operators included."""
    g, dg, _, mesh = setup
    placement = _partition(g, 3, seed=0)
    starts = np.arange(0, g.n_nodes, 4, dtype=np.int32)
    for q in QUERIES:
        ca = paa.compile_query(q, g)
        acc_sh, _ = strategies.s2_execute(
            mesh, placement, ca, starts, backend="frontier_kernel_sharded", block_size=8
        )
        acc_gl, _ = strategies.s2_execute(
            mesh, placement, ca, starts, backend="frontier_kernel", block_size=8
        )
        assert (acc_sh == acc_gl).all(), q
        for i, s in enumerate(starts):
            want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
            assert (acc_sh[i] == want).all(), (q, int(s))


def test_per_site_meters_sum_to_host_meter(setup):
    """On a disjoint partition (K=1) the per-site response meters sum to
    the instrumented host meter symbol-for-symbol, and the broadcast side
    matches exactly (broadcasts are global, responses site-local)."""
    g, _, index, mesh = setup
    placement = _partition(g, 3, seed=1)
    starts = np.arange(g.n_nodes, dtype=np.int32)
    for q in ["(l0|l1)* l2 .^-1", ". l1"]:
        ca = paa.compile_query(q, g)
        _, costs = strategies.s2_execute(
            mesh, placement, ca, starts, backend="frontier_kernel_sharded", block_size=8
        )
        for s in starts:
            host = strategies.s2_costs(ca, index, int(s))
            c = costs[s]
            assert len(c.site_unicast_symbols) == 3, (q, int(s))
            assert sum(c.site_unicast_symbols) == host.unicast_symbols, (q, int(s))
            assert c.unicast_symbols == host.unicast_symbols, (q, int(s))  # K=1
            assert c.broadcast_symbols == host.broadcast_symbols, (q, int(s))
            assert c.n_broadcasts == host.n_broadcasts, (q, int(s))


def test_per_site_meters_under_replication(setup):
    """With replicated edges every holding site answers, so the per-site
    sum is the K-weighted total: s2_execute's single-copy normalization
    divides it back out, and the sum stays within the per-edge
    replication spread of the host meter."""
    g, _, index, mesh = setup
    placement = distribute(g, n_sites=4, replication_rate=0.5, seed=4)
    ca = paa.compile_query("(l0|l1)* l2 .^-1", g)
    _, costs = strategies.s2_execute(
        mesh, placement, ca, np.array([0, 7], np.int32),
        backend="frontier_kernel_sharded", block_size=8,
    )
    k = placement.replication.astype(float)
    spread = k.max() / max(k.min(), 1.0)
    for c, s in zip(costs, (0, 7)):
        host = strategies.s2_costs(ca, index, s)
        total = sum(c.site_unicast_symbols)
        assert total == pytest.approx(c.unicast_symbols * placement.replication_factor)
        assert total <= host.unicast_symbols * spread * placement.replication_factor + 1e-6
        assert c.broadcast_symbols == host.broadcast_symbols


def test_site_aware_cost_of_uses_measured_sum(setup):
    """cost_model.cost_of prefers the measured per-site response total
    over the N_p·k·D_s2 estimate when a cost carries one."""
    from repro.core import cost_model

    net = cost_model.NetworkParams(n_peers=100, n_connections=300, replication_rate=0.2)
    est = strategies.StrategyCost("S2", broadcast_symbols=5.0, unicast_symbols=30.0)
    meas = strategies.StrategyCost(
        "S2", broadcast_symbols=5.0, unicast_symbols=30.0,
        site_unicast_symbols=(40.0, 20.0, 30.0),
    )
    bc = net.n_peers * 2.0 * net.mean_degree * 5.0
    assert cost_model.cost_of(net, est) == pytest.approx(bc + 100 * 0.2 * 30.0)
    assert cost_model.cost_of(net, meas) == pytest.approx(bc + 90.0)


def test_sharded_plan_bucket_invariants(setup):
    """Shape-bucketed plans: bucket assignment is deterministic, shape
    classes of multi-member buckets are powers of two (a singleton
    bucket has nothing to unify and keeps its natural shape), every
    site's useful steps fit its bucket, padding steps are
    valid=0/first=0 zero-tile no-ops on the last output block, and
    each site's real prefix still covers every (dst_state, block_col)
    block."""
    g, _, _, _ = setup
    placement = _partition(g, 3, seed=2)
    ca = paa.compile_query("l0 (l1|l2)* l0", g)
    site_graphs = [placement.local_graph(s) for s in range(3)]
    plan = build_sharded_level_plan(ca, site_graphs, block_size=8)
    plan2 = build_sharded_level_plan(ca, site_graphs, block_size=8)
    nb = plan.v_pad // plan.block_size

    # deterministic assignment: two builds agree bucket-for-bucket
    assert plan.bucket_shapes == plan2.bucket_shapes
    assert [b.sites for b in plan.buckets] == [b.sites for b in plan2.buckets]
    assert plan.padded_steps >= plan.useful_steps > 0
    assert plan.pad_waste_ratio >= 1.0

    # the fixture must exercise both shapes: shared (pow2) and singleton
    assert any(len(b.sites) > 1 for b in plan.buckets)

    seen_sites = []
    for b in plan.buckets:
        if len(b.sites) > 1:  # shared program: power-of-two classes
            assert b.n_steps & (b.n_steps - 1) == 0
            assert b.n_tiles & (b.n_tiles - 1) == 0
        assert b.step.shape == b.src.shape == b.dst.shape == (len(b.sites), b.n_steps)
        assert b.tiles.shape[:2] == (len(b.sites), b.n_tiles)
        assert (np.asarray(b.tiles)[:, 0] == 0).all()  # zero cover tile
        c = unpack_schedule(b.step, b.src, b.dst)
        orows, ocols, tids, firsts, valids = (
            c[k] for k in ("o_rows", "o_cols", "tile_ids", "firsts", "valids"))
        for row, s in enumerate(b.sites):
            seen_sites.append(s)
            key = orows[row].astype(np.int64) * nb + ocols[row]
            assert (np.diff(key) >= 0).all(), s  # sorted incl. padding tail
            blocks = set(zip(orows[row].tolist(), ocols[row].tolist()))
            assert blocks == {(q, c) for q in range(ca.n_states) for c in range(nb)}, s
            assert firsts[row].sum() == ca.n_states * nb, s
            # the site's own (unpadded) schedule fits its bucket; the
            # padding tail multiplies the zero cover tile into the last
            # output block with first=0 AND valid=0 (in-kernel skip)
            own_plan = build_sharded_level_plan(ca, [site_graphs[s]], block_size=8)
            own_len = int(own_plan.useful_steps)
            assert own_len <= b.n_steps, s
            if len(b.sites) == 1:  # singleton: natural shape, no roundup
                assert b.n_steps == own_len, s
                assert b.n_tiles == own_plan.buckets[0].n_tiles, s
                assert own_plan.pad_waste_ratio == 1.0, s
            assert (tids[row][own_len:] == 0).all(), s
            assert (firsts[row][own_len:] == 0).all(), s
            assert (valids[row][own_len:] == 0).all(), s
            assert (orows[row][own_len:] == ca.n_states - 1).all(), s
            assert (ocols[row][own_len:] == nb - 1).all(), s
            # valid steps are exactly the site's real-tile steps
            assert valids[row].sum() == plan.n_real_steps[s], s
    assert sorted(seen_sites) == [0, 1, 2]  # every site in exactly one bucket


def test_sharded_requires_placement_and_divisible_sites(setup):
    g, _, _, mesh = setup
    ca = paa.compile_query("l0", g)
    with pytest.raises(ValueError, match="placement"):
        strategies.make_s2_step_fn(
            ca, g.n_nodes, mesh, backend="frontier_kernel_sharded"
        )


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.multidevice
def test_sharded_backend_on_8_devices():
    """Acceptance criterion: on ≥2 real (forced-host) devices the sharded
    backend still matches the reference BFS and the global fused backend
    bit-exactly, with per-site meters summing to the host meter."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        from repro.core import paa, strategies
        from repro.dist import compat
        from repro.graph.generators import random_labeled_graph
        from repro.graph.partition import Placement, distribute
        from repro.graph.structure import to_device_graph

        assert len(jax.devices()) == 8
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g = random_labeled_graph(40, 170, 4, seed=9)
        dg = to_device_graph(g)
        index = paa.HostIndex(g)
        starts = np.arange(0, 40, 5, dtype=np.int32)

        # disjoint partition, one site per data-axis device
        rng = np.random.default_rng(0)
        assign = rng.integers(0, 4, g.n_edges)
        site_edges = [np.nonzero(assign == s)[0].astype(np.int64) for s in range(4)]
        placement = Placement(g, 4, site_edges, np.ones(g.n_edges, np.int32))
        ca = paa.compile_query("(l0|l1)* l2 .^-1", g)
        acc, costs = strategies.s2_execute(
            mesh, placement, ca, starts,
            backend="frontier_kernel_sharded", block_size=8)
        acc_gl, _ = strategies.s2_execute(
            mesh, placement, ca, starts, backend="frontier_kernel", block_size=8)
        assert (acc == acc_gl).all()
        for i, s in enumerate(starts):
            want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
            assert (acc[i] == want).all(), int(s)
            host = strategies.s2_costs(ca, index, int(s))
            assert sum(costs[i].site_unicast_symbols) == host.unicast_symbols, int(s)
            assert costs[i].broadcast_symbols == host.broadcast_symbols, int(s)

        # replicated placement, 8 sites blocked 2-per-device
        placement2 = distribute(g, n_sites=8, replication_rate=0.3, seed=9)
        ca2 = paa.compile_query("l0 (l1|l2)* l3", g)
        acc2, costs2 = strategies.s2_execute(
            mesh, placement2, ca2, starts,
            backend="frontier_kernel_sharded", block_size=8)
        for i, s in enumerate(starts):
            want = np.asarray(paa.answers_single_source(ca2, dg, int(s)))
            assert (acc2[i] == want).all(), int(s)
            assert len(costs2[i].site_unicast_symbols) == 8
            k = placement2.replication_factor
            assert abs(sum(costs2[i].site_unicast_symbols)
                       - costs2[i].unicast_symbols * k) < 1e-3
        print("SHARDED_MULTIDEVICE_OK")
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
    assert res.returncode == 0 and "SHARDED_MULTIDEVICE_OK" in res.stdout, (
        f"8-device subprocess failed (rc={res.returncode})\n"
        f"--- child stdout ---\n{res.stdout}\n--- child stderr ---\n{res.stderr}"
    )
