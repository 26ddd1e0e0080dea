"""Bitpacked (uint32) adjacency tile store + out-of-core Stage A.

Covers the PR-10 contracts: bit-plane packing is byte-exact against the
f32 store at 1/32 the bytes (chunked staging included), every S2 backend
answers bit-exactly on either store, witness/counting semantics refuse
or fall back off the boolean-only packed tiles, and the byte-budgeted
slab cache spills cold (direction, label) slabs to disk and restores
them byte-identically (``BUILD_COUNTERS["spills"/"reloads"]`` asserted).
"""

import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import paa
from repro.core.automaton import FWD, INV
from repro.core.cost_model import NetworkParams
from repro.core.plans import GraphPlanStore
from repro.dist import compat
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import distribute
from repro.graph.structure import LabeledGraph, to_device_graph
from repro.kernels.frontier import ops as fops
from repro.kernels.frontier.ref import (
    pack_blocks,
    pack_blocks_chunked,
    tile_words,
    unpack_tiles,
)
from repro.serve import QueryService, ServeConfig

NET = NetworkParams(n_peers=150, n_connections=450, replication_rate=0.2)

S2_BACKENDS = [
    "reference",
    "frontier_kernel",
    "frontier_kernel_packed",
    "frontier_kernel_sharded",
]


def _graph(seed=3, n_nodes=60, n_edges=260, n_labels=4):
    return random_labeled_graph(n_nodes, n_edges, n_labels, seed=seed)


def _oracle(g, query, starts):
    dg = to_device_graph(g)
    ca = paa.compile_query(query, g)
    return [
        set(
            np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[
                0
            ].tolist()
        )
        for s in starts
    ]


# ---------------------------------------------------------------------------
# packing: bit-plane byte identity + the 32x ratio
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", [32, 64, 128])
def test_pack_blocks_uint32_is_bit_identical_at_1_32_bytes(block):
    """uint32 packing lands the same block layout as f32 and unpacks to
    the exact same dense tiles, at tile_words(B)/B of the bytes (1/32
    when 32 | B)."""
    rng = np.random.default_rng(11)
    src = rng.integers(0, 500, 3000).astype(np.int32)
    dst = rng.integers(0, 500, 3000).astype(np.int32)
    tf, rf, cf, vp_f = pack_blocks(src, dst, 500, block)
    tu, ru, cu, vp_u = pack_blocks(src, dst, 500, block, "uint32")
    assert vp_f == vp_u
    np.testing.assert_array_equal(rf, ru)
    np.testing.assert_array_equal(cf, cu)
    assert tu.dtype == np.uint32 and tu.shape == (tf.shape[0], tile_words(block), block)
    np.testing.assert_array_equal(unpack_tiles(tu, block), tf)
    assert tf.nbytes == 32 * tu.nbytes  # 32 | block for every swept size


def test_pack_blocks_uint32_keeps_duplicate_edge_bits():
    """Duplicate edges must OR into the word plane, not overwrite it
    (``np.bitwise_or.at``, not fancy assignment)."""
    src = np.array([0, 0, 0, 1], np.int32)
    dst = np.array([5, 5, 37, 5], np.int32)
    tu, _, _, _ = pack_blocks(src, dst, 64, 64, "uint32")
    dense = unpack_tiles(tu, 64)
    assert dense[0, 0, 5] == 1.0 and dense[0, 0, 37] == 1.0 and dense[0, 1, 5] == 1.0


def test_pack_blocks_chunked_uint32_byte_identical_to_one_shot():
    rng = np.random.default_rng(4)
    src = rng.integers(0, 300, 2200).astype(np.int32)
    dst = rng.integers(0, 300, 2200).astype(np.int32)
    t1, r1, c1, _ = pack_blocks(src, dst, 300, 64, "uint32")
    t2, r2, c2, _, n_chunks = pack_blocks_chunked(src, dst, 300, 64, 500, "uint32")
    assert n_chunks == 5
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(c1, c2)


def test_stage_graph_uint32_matches_f32_store():
    """Full staging at uint32: same offset keys, same block coordinates,
    unpacked tiles byte-equal to the f32 staging (any-label union stores
    included), slab byte accounting at the packed ratio."""
    g = _graph()
    sf = fops.stage_graph(g, 16)
    su = fops.stage_graph(g, 16, tile_dtype="uint32")
    assert su.tile_dtype == "uint32" and sf.tile_dtype == "f32"
    assert sf.offsets.keys() == su.offsets.keys()
    assert (FWD, fops.ANY_LABEL) in su.offsets and (INV, fops.ANY_LABEL) in su.offsets
    np.testing.assert_array_equal(
        unpack_tiles(np.asarray(su.tiles), 16), np.asarray(sf.tiles)
    )
    for k in sf.offsets:
        np.testing.assert_array_equal(sf.offsets[k][1], su.offsets[k][1])
        np.testing.assert_array_equal(sf.offsets[k][2], su.offsets[k][2])
    ratio = 16 / tile_words(16)  # B=16 packs into 1 word: 16x, not 32x
    assert sf.tile_store_bytes == ratio * su.tile_store_bytes
    for k, nbytes in su.slab_bytes().items():
        assert sf.slab_bytes()[k] == ratio * nbytes


def test_staged_chunked_uint32_byte_identical():
    g = _graph(seed=8, n_edges=400)
    one = fops.stage_graph(g, 16, tile_dtype="uint32")
    chunked = fops.stage_graph(g, 16, chunk_edges=64, tile_dtype="uint32")
    assert chunked.staging_chunks > 0
    np.testing.assert_array_equal(np.asarray(one.tiles), np.asarray(chunked.tiles))
    assert one.offsets.keys() == chunked.offsets.keys()


def test_blocked_graph_source_refuses_uint32():
    g = _graph()
    bg = fops.make_blocked_graph(g, 16)
    with pytest.raises(ValueError, match="pre-packed f32"):
        fops.stage_graph(bg, 16, tile_dtype="uint32")


# ---------------------------------------------------------------------------
# executors: bit-exact answers on every backend, both stores
# ---------------------------------------------------------------------------

QUERIES = ["l0 (l1|l2)* l3", "(l0|l1)+", "l0* l3^-1", ". l1"]


@pytest.mark.parametrize("backend", S2_BACKENDS)
def test_backend_bit_exact_on_uint32_store(backend):
    """Every S2 backend answers bit-exactly vs the host PAA with the
    uint32 tile store configured (reference ignores tiles — included to
    pin the config path end to end)."""
    g = _graph()
    placement = distribute(g, n_sites=4, replication_rate=0.3, seed=2)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    starts = np.arange(0, g.n_nodes, 7, dtype=np.int32)
    svc = QueryService(
        placement, mesh, NET,
        config=ServeConfig(
            n_rollouts=50, seed=0, s2_backend=backend, s2_block_size=16,
            s2_tile_dtype="uint32",
        ),
    )
    for q in QUERIES:
        ans = svc.submit(q, starts, strategy="S2")
        assert ans.answers == _oracle(g, q, starts), (backend, q)


def test_signature_distinguishes_tile_dtype():
    from repro.serve.plancache import automaton_signature

    g = _graph()
    ca = paa.compile_query("l0 l1", g)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    s_f = automaton_signature(ca, g.n_nodes, mesh, backend="frontier_kernel")
    s_u = automaton_signature(
        ca, g.n_nodes, mesh, backend="frontier_kernel", tile_dtype="uint32"
    )
    assert s_f != s_u and s_f[:-1] == s_u[:-1]  # dtype appended at the END


# ---------------------------------------------------------------------------
# semiring contracts: refusal at the ops layer, fallback at strategies
# ---------------------------------------------------------------------------


def test_witness_and_counting_wrappers_refuse_uint32_plans():
    g = _graph()
    ca = paa.compile_query("l0 l1", g)
    staged = fops.stage_graph(g, 16, tile_dtype="uint32")
    plan = fops.build_level_schedule(ca, staged)
    assert plan.tile_dtype == "uint32"
    f32_frontier = jnp.zeros((ca.n_states * plan.q_pad, plan.v_pad), jnp.float32)
    u32_frontier = jnp.zeros((ca.n_states * plan.q_pad, plan.v_pad), jnp.uint32)
    with pytest.raises(ValueError, match="f32 tile store"):
        fops.reach_fixpoint_levels(plan, f32_frontier, interpret=True)
    with pytest.raises(ValueError, match="f32 tile store"):
        fops.reach_fixpoint_packed_levels(plan, u32_frontier, interpret=True)
    with pytest.raises(ValueError, match="f32 tile store"):
        fops.count_paths_bounded(plan, f32_frontier, tuple(ca.accepting), 3)


def test_witness_semantics_falls_back_to_f32_staging():
    """A witness request on a uint32-configured service restages f32 —
    answers AND witness levels come back, and the plan store holds an
    f32 Stage-A entry for the fallback."""
    g = _graph()
    placement = distribute(g, n_sites=4, replication_rate=0.3, seed=2)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    svc = QueryService(
        placement, mesh, NET,
        config=ServeConfig(
            n_rollouts=50, seed=0, s2_backend="frontier_kernel_packed",
            s2_block_size=16, s2_tile_dtype="uint32",
        ),
    )
    ans = svc.submit("l0 (l1|l2)* l3", [0, 5], semantics="witness", strategy="S2")
    assert ans.levels is not None
    assert ans.answers == _oracle(g, "l0 (l1|l2)* l3", np.array([0, 5]))
    ts = svc.exec_cache.plan_store.tile_store_stats()
    assert ts["bytes_by_dtype"]["f32"] > 0  # the witness fallback staging


# ---------------------------------------------------------------------------
# out-of-core: budgeted slab cache, spill -> reload byte identity
# ---------------------------------------------------------------------------


def test_slab_cache_spills_and_reloads_byte_identically():
    g = _graph(seed=5, n_nodes=200, n_edges=1200, n_labels=4)
    store = GraphPlanStore()
    full = store.staged_graph(g, 32, tile_dtype="uint32")
    full_np = np.asarray(full.tiles)

    fops.reset_build_counters()
    budget = full.tile_store_bytes // 3  # well under the full store
    keys_a = ((FWD, 0), (FWD, 1), (FWD, fops.ANY_LABEL))
    keys_b = ((INV, 0), (INV, 2), (INV, fops.ANY_LABEL))

    def check(staged, keys):
        for k in keys:
            base_f, rows_f, cols_f = full.offsets[k]
            base_s, rows_s, cols_s = staged.offsets[k]
            np.testing.assert_array_equal(rows_f, rows_s)
            np.testing.assert_array_equal(cols_f, cols_s)
            np.testing.assert_array_equal(
                full_np[base_f : base_f + len(rows_f)],
                np.asarray(staged.tiles)[base_s : base_s + len(rows_s)],
            )

    check(
        store.staged_graph(
            g, 32, tile_dtype="uint32", budget_bytes=budget, keys=keys_a
        ),
        keys_a,
    )
    # touching a disjoint key set forces the first set cold -> spilled
    check(
        store.staged_graph(
            g, 32, tile_dtype="uint32", budget_bytes=budget, keys=keys_b
        ),
        keys_b,
    )
    assert fops.BUILD_COUNTERS["spills"] > 0
    # and back: the spilled slabs reload from disk, byte-identical
    check(
        store.staged_graph(
            g, 32, tile_dtype="uint32", budget_bytes=budget, keys=keys_a
        ),
        keys_a,
    )
    assert fops.BUILD_COUNTERS["reloads"] > 0

    ts = store.tile_store_stats()
    assert ts["spills"] > 0 and ts["reloads"] > 0
    assert ts["bytes_by_dtype"]["uint32"] > 0


def test_slab_cache_rebuilds_from_edges_when_spill_file_is_gone():
    import os

    g = _graph(seed=6, n_nodes=150, n_edges=900)
    store = GraphPlanStore()
    full = store.staged_graph(g, 32, tile_dtype="uint32")
    budget = full.tile_store_bytes // 4
    keys_a = ((FWD, 0), (FWD, 1))
    keys_b = ((INV, 0), (INV, 1))
    store.staged_graph(g, 32, tile_dtype="uint32", budget_bytes=budget, keys=keys_a)
    store.staged_graph(g, 32, tile_dtype="uint32", budget_bytes=budget, keys=keys_b)
    cache = store._slab_cache(g, 32, 0, None, "uint32")
    assert cache.spilled_slabs() > 0
    for path in cache._spilled.values():  # simulate losing the spill dir
        if os.path.exists(path):
            os.unlink(path)
    reloads_before = cache.reloads
    staged = store.staged_graph(
        g, 32, tile_dtype="uint32", budget_bytes=budget, keys=keys_a
    )
    assert cache.reloads == reloads_before  # no file -> rebuild, not reload
    for k in keys_a:
        base_f, rows_f, _ = full.offsets[k]
        base_s, rows_s, _ = staged.offsets[k]
        np.testing.assert_array_equal(
            np.asarray(full.tiles)[base_f : base_f + len(rows_f)],
            np.asarray(staged.tiles)[base_s : base_s + len(rows_s)],
        )


def test_budgeted_query_stream_bit_exact_with_spills():
    """Acceptance: under a budget smaller than the full staged tensor, a
    query stream over ALL labels still answers bit-exactly, with the
    spill + reload path actually exercised."""
    g = _graph(seed=7, n_nodes=120, n_edges=700, n_labels=4)
    placement = distribute(g, n_sites=4, replication_rate=0.3, seed=3)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    full = fops.stage_graph(g, 16, tile_dtype="uint32")
    budget = full.tile_store_bytes // 3
    svc = QueryService(
        placement, mesh, NET,
        config=ServeConfig(
            n_rollouts=50, seed=0, s2_backend="frontier_kernel_packed",
            s2_block_size=16, s2_tile_dtype="uint32",
            tile_store_budget_bytes=budget,
        ),
    )
    fops.reset_build_counters()
    starts = np.arange(0, g.n_nodes, 11, dtype=np.int32)
    # one query per label plus inverses/wildcards: every slab gets hot,
    # then cold, as the stream sweeps the label space
    stream = [
        "l0+", "l1+", "l2+", "l3+",
        "l0^-1 l1", "l2^-1 l3", ". l0", "l3 .^-1",
        "l0+", "l2+",  # back to evicted slabs -> reload/rebuild
    ]
    for q in stream:
        ans = svc.submit(q, starts, strategy="S2")
        assert ans.answers == _oracle(g, q, starts), q
    assert fops.BUILD_COUNTERS["spills"] > 0
    assert fops.BUILD_COUNTERS["reloads"] > 0
    fm = svc.exec_cache.frontier_mem_stats()
    assert fm["tile_store"]["spills"] > 0
    assert fm["tile_store"]["reloads"] > 0
    assert fm["tile_store"]["bytes_by_dtype"]["uint32"] <= budget


def test_frontier_mem_stats_reports_tile_store_bytes_per_dtype():
    from repro.serve.metrics import _empty_frontier_mem_stats
    from repro.serve.plancache import ExecutorCache

    g = _graph()
    cache = ExecutorCache()
    cache.plan_store.staged_graph(g, 16)
    cache.plan_store.staged_graph(g, 16, tile_dtype="uint32")
    out = cache.frontier_mem_stats()
    schema = _empty_frontier_mem_stats()
    assert set(out) == set(schema)
    assert set(out["tile_store"]) == set(schema["tile_store"])
    assert out["tile_store"]["bytes_by_dtype"]["f32"] > 0
    assert out["tile_store"]["bytes_by_dtype"]["uint32"] > 0
    # the two stores cache independently under dtype-distinct keys
    assert (
        out["tile_store"]["bytes_by_dtype"]["f32"]
        == 16 * out["tile_store"]["bytes_by_dtype"]["uint32"]  # B=16 -> 1 word
    )


def test_persist_roundtrip_preserves_tile_dtype(tmp_path):
    from repro.serve import persist

    g = _graph()
    placement = distribute(g, n_sites=2, replication_rate=0.0, seed=1)
    store = GraphPlanStore()
    store.staged_graph(placement.graph, 16, tile_dtype="uint32")
    store.staged_sharded(placement, 16, tile_dtype="uint32")
    path = str(tmp_path / "stage_a.snap")
    manifest = persist.save_stage_a(store, placement, path)
    assert manifest["n_entries"] == 2

    fresh = GraphPlanStore()
    assert persist.load_stage_a(fresh, placement, path)
    fops.reset_build_counters()
    warm = fresh.staged_graph(placement.graph, 16, tile_dtype="uint32")
    assert warm.tile_dtype == "uint32"
    assert np.asarray(warm.tiles).dtype == np.uint32
    assert fops.BUILD_COUNTERS["pack_blocks"] == 0  # warm: zero packing
    warm_sh = fresh.staged_sharded(placement, 16, tile_dtype="uint32")
    assert warm_sh.tile_dtype == "uint32"


# ---------------------------------------------------------------------------
# 8-device subprocess: uint32 store across a real mesh
# ---------------------------------------------------------------------------

CHILD_ENV = {
    "PYTHONPATH": "src",
    "PATH": "/usr/bin:/bin:/usr/local/bin",
    "HOME": "/root",
    "JAX_PLATFORMS": "cpu",
}
SUBPROCESS_TIMEOUT_S = 600


@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.multidevice
@pytest.mark.timeout_s(SUBPROCESS_TIMEOUT_S + 60)
def test_uint32_store_bit_exact_on_8_devices():
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        from repro.core import paa, strategies
        from repro.core.plans import GraphPlanStore
        from repro.dist import compat
        from repro.graph.generators import random_labeled_graph
        from repro.graph.partition import distribute
        from repro.graph.structure import to_device_graph

        assert len(jax.devices()) == 8
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g = random_labeled_graph(48, 220, 4, seed=9)
        placement = distribute(g, n_sites=8, replication_rate=0.3, seed=9)
        dg = to_device_graph(g)
        store = GraphPlanStore()
        starts = np.arange(0, 48, 6, dtype=np.int32)

        for query in ["l0 (l1|l2)* l3", "(l0|l1)+ l2^-1"]:
            ca = paa.compile_query(query, g)
            want = np.stack([
                np.asarray(paa.answers_single_source(ca, dg, int(s)))
                for s in starts
            ])
            for backend in ["frontier_kernel", "frontier_kernel_packed",
                            "frontier_kernel_sharded"]:
                for dtype in ["f32", "uint32"]:
                    out = strategies.s2_execute(
                        mesh, placement, ca, starts,
                        backend=backend, block_size=16, plan_store=store,
                        tile_dtype=dtype,
                    )
                    acc = np.asarray(out[0])
                    assert (acc == want).all(), (query, backend, dtype)
        ts = store.tile_store_stats()
        assert ts["bytes_by_dtype"]["uint32"] > 0
        assert ts["bytes_by_dtype"]["f32"] > 0
        print("TILESTORE_8DEV_OK")
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
    assert res.returncode == 0 and "TILESTORE_8DEV_OK" in res.stdout, (
        f"8-device subprocess failed (rc={res.returncode})\n"
        f"--- child stdout ---\n{res.stdout}\n--- child stderr ---\n{res.stderr}"
    )
