"""The LDBC SNB deployment and the lane-dense uint32 tile store.

Covers the social-network generator (``generators.ldbc_snb_like``: its 15
labels, symmetric power-law ``knows``, ids grouped by type, seeded), the
served answers of the benchmark's LDBC path queries against the host PAA
on the packed executor over the uint32 store, both uint32 level kernels
against dense products of ``ref.unpack_tiles`` on every staging path
(global, per-site, merged, bucketed), the level kernel's byte count
against its BlockSpec, and the rebuild of a Stage-A snapshot written in
the old tile layout.
"""

import asyncio
import os
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import paa
from repro.core.automaton import FWD
from repro.core.cost_model import NetworkParams
from repro.core.plans import GraphPlanStore
from repro.dist import compat
from repro.graph import generators
from repro.graph.generators import LDBC_LABELS, ldbc_snb_like
from repro.graph.partition import distribute
from repro.graph.structure import to_device_graph
from repro.kernels.frontier import ops as fops
from repro.kernels.frontier.frontier import (
    fused_level_blocks, pack_schedule, packed_level_blocks, unpack_schedule,
)
from repro.kernels.frontier.ref import tile_words, unpack_tiles
from repro.serve import AsyncQueryService, QueryService, ServeConfig, persist

NET = NetworkParams(n_peers=150, n_connections=450, replication_rate=0.2)

# the queries of bench/traffic/paths_closed.json
QUERIES = [
    "isPartOf^-1 isLocatedIn^-1 knows+",
    "isPartOf^-1 isLocatedIn^-1 knows knows? hasCreator^-1 hasTag",
    "isPartOf^-1 isLocatedIn^-1 (knows | hasMember^-1 hasModerator)+",
    "isPartOf^-1 isLocatedIn^-1 workAt^-1 knows+",
    "isPartOf^-1 isLocatedIn^-1 likes hasCreator knows",
    "hasType^-1 hasTag^-1 hasCreator",
    "isSubclassOf^-1* hasType^-1 hasTag^-1 replyOf^-1+ hasCreator",
    "hasType^-1 hasInterest^-1 knows hasMember^-1 containerOf hasCreator",
]
BLOCK = 64


def tiny_ldbc(seed: int):
    """An LDBC-like graph of a few hundred nodes: SF0.0003, with Datagen's
    fixed dictionaries (25k places, organisations and tags) cut to 0.5%."""
    small = {k: max(round(n * 0.005), 3) for k, n in generators.LDBC_STATIC.items()}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(generators, "LDBC_STATIC", small)
        return ldbc_snb_like(scale_factor=0.0003, seed=seed)


@pytest.fixture(scope="module")
def tiny():
    return tiny_ldbc(1)


def _label(g, name):
    return g.lbl == g.label_to_id[name]


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


def test_ldbc_has_the_schema_labels(tiny):
    assert tiny.labels == LDBC_LABELS and len(set(tiny.labels)) == 15
    assert np.all(np.bincount(tiny.lbl, minlength=15) > 0)


def test_ldbc_knows_is_symmetric_and_heavy_tailed():
    g = ldbc_snb_like(0.03, seed=0)
    sel = _label(g, "knows")
    pairs = set(zip(g.src[sel].tolist(), g.dst[sel].tolist()))
    assert pairs == {(b, a) for a, b in pairs} and all(a != b for a, b in pairs)
    persons = np.unique(g.src[sel])

    def tail(name):  # max over mean out-degree, over the persons
        d = np.bincount(g.src[_label(g, name)], minlength=g.n_nodes)[persons]
        return d.max() / d.mean()

    # power-law friendship against the labels drawn per person alone
    assert tail("knows") > 5.0
    assert all(tail(name) < 0.5 * tail("knows") for name in ("hasInterest", "studyAt", "workAt"))


def test_ldbc_ids_are_grouped_by_type(tiny):
    g = tiny

    def ends(name, side):
        return (g.src if side == "src" else g.dst)[_label(g, name)]

    places = np.concatenate([ends("isPartOf", "src"), ends("isPartOf", "dst")])
    types = [  # in id order, as the generator numbers them
        places,
        ends("studyAt", "dst"),  # universities
        ends("workAt", "dst"),  # companies
        np.concatenate([ends("hasType", "dst"), ends("isSubclassOf", "src")]),  # tag classes
        ends("hasType", "src"),  # tags
        np.concatenate([ends("knows", "src"), ends("hasModerator", "dst")]),  # persons
        ends("hasModerator", "src"),  # forums
        ends("containerOf", "dst"),  # posts
        ends("replyOf", "src"),  # comments
    ]
    for lower, upper in zip(types, types[1:]):
        assert lower.max() < upper.min()
    # a post's forum and a comment's thread sit next to it: containerOf
    # and replyOf to a post keep id order
    fo, po = ends("containerOf", "src"), ends("containerOf", "dst")
    assert np.all(np.diff(fo[np.argsort(po)]) >= 0)


def test_ldbc_is_seeded():
    a, b = tiny_ldbc(5), tiny_ldbc(5)
    c = tiny_ldbc(6)
    assert a.n_nodes == b.n_nodes
    for k in ("src", "lbl", "dst"):
        assert getattr(a, k).tobytes() == getattr(b, k).tobytes()
    assert a.src.tobytes() != c.src.tobytes()


# ---------------------------------------------------------------------------
# served answers: QueryService and AsyncQueryService over the uint32 store
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def service(tiny):
    placement = distribute(tiny, n_sites=4, replication_rate=0.3, seed=2)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = ServeConfig(
        n_rollouts=20, seed=0, s2_backend="frontier_kernel_packed",
        s2_tile_dtype="uint32", s2_block_size=BLOCK,
    )
    return QueryService(placement, mesh, NET, config=cfg)


def _starts(g, query, k=6):
    """Up to ``k`` of the query's valid starts: nodes with an edge a move
    out of the start state takes (every one of them for the smallest)."""
    ca = paa.compile_query(query, g)
    has = np.zeros(g.n_nodes, bool)
    for t in ca.transitions:
        if t.src == ca.start:
            sel = g.lbl == t.label_id
            has[(g.src if t.direction == FWD else g.dst)[sel]] = True
    starts = np.nonzero(has)[0]
    assert len(starts) > 0, query
    return starts[np.linspace(0, len(starts) - 1, min(k, len(starts))).astype(int)]


def _oracle(g, query, starts):
    dg = to_device_graph(g)
    ca = paa.compile_query(query, g)
    return [
        set(np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist())
        for s in starts
    ]


@pytest.mark.parametrize("query", QUERIES)
def test_ldbc_query_served_equals_paa(service, tiny, query):
    starts = _starts(tiny, query)
    ans = service.submit(query, starts, strategy="S2")
    assert ans.strategy == "S2"
    assert [set(a) for a in ans.answers] == _oracle(tiny, query, starts)


def test_ldbc_queries_through_the_async_front_end(service, tiny):
    picked = [(q, _starts(tiny, q, k=3)) for q in QUERIES]

    async def drive():
        async with AsyncQueryService(service) as aio:
            return await asyncio.gather(
                *(aio.submit(q, s, slo="throughput", strategy="S2") for q, s in picked)
            )

    for (query, starts), ans in zip(picked, asyncio.run(drive())):
        assert [set(a) for a in ans.answers] == _oracle(tiny, query, starts), query


# ---------------------------------------------------------------------------
# the uint32 level kernels on every staging path
# ---------------------------------------------------------------------------

PATH_QUERY = "isPartOf^-1 isLocatedIn^-1 (knows | hasMember^-1 hasModerator)+ likes"
KB = 32  # block size of the kernel checks: one word row a tile


def _dense_level(frontier, dense, words, n_out_rows, q_pad, packed):
    """The level as dense numpy products over ``unpack_tiles`` tiles."""
    c = unpack_schedule(*words)
    vl, ti, fr, fc, orw, oc = (c[k] for k in (
        "valids", "tile_ids", "f_rows", "f_cols", "o_rows", "o_cols"))
    f = np.asarray(frontier)
    out = np.zeros((n_out_rows, f.shape[1]), f.dtype)
    for i in np.nonzero(vl)[0]:
        blk = f[fr[i] * q_pad : (fr[i] + 1) * q_pad, fc[i] * KB : (fc[i] + 1) * KB]
        a = dense[ti[i]] > 0
        rows = slice(orw[i] * q_pad, (orw[i] + 1) * q_pad)
        cols = slice(oc[i] * KB, (oc[i] + 1) * KB)
        if packed:
            out[rows, cols] |= np.bitwise_or.reduce(
                np.where(a[:, None, :], blk.T[:, :, None], np.uint32(0)), axis=0
            )
        else:
            out[rows, cols] += blk @ a.astype(np.float32)
    return out


def _schedules(path, g, ca):
    """(tiles, step words) pairs of one staging path, uint32 store."""
    nb = -(-g.n_nodes // KB)
    if path == "global":
        plan = fops.build_level_schedule(ca, fops.stage_graph(g, KB, tile_dtype="uint32"))
        return [(plan.tiles, (plan.step, plan.src, plan.dst))]
    placement = distribute(g, n_sites=4, replication_rate=0.3, seed=3)
    sites = [placement.local_graph(s) for s in range(4)]
    staged = fops.stage_sharded_graph(sites, KB, "uint32")
    if path == "per_site":
        frow_map, _ = fops.fanin_frontier_rows(ca)
        out = []
        for tiles, offsets in zip(staged.site_tiles, staged.site_offsets):
            arr, fi, vl, _ = fops._schedule_steps(ca, offsets, nb, frow_map)
            out.append((jnp.asarray(tiles), pack_schedule(
                fi, vl, arr[:, 4], arr[:, 2], arr[:, 3], arr[:, 0], arr[:, 1])))
        return out
    if path == "merged":
        staged = fops.merge_staged_sites(staged, 2)
    plan = fops.build_sharded_level_schedule(ca, staged, bucket_floor=64)
    if path == "bucketed":  # the floor pads member sites into shared classes
        assert any(len(b.sites) > 1 for b in plan.buckets)
    return [
        (b.tiles[r], (b.step[r], b.src[r], b.dst[r]))
        for b in plan.buckets for r in range(len(b.sites))
    ]


@pytest.mark.parametrize("kernel", ["packed", "fused"])
@pytest.mark.parametrize("path", ["global", "per_site", "merged", "bucketed"])
def test_u32_kernels_equal_unpacked_products(tiny, path, kernel):
    ca = paa.compile_query(PATH_QUERY, tiny)
    n_union = len(fops.fanin_frontier_rows(ca)[1])
    q_pad = fops.QPAD
    v_pad = -(-tiny.n_nodes // KB) * KB
    rng = np.random.default_rng(4)
    n_rows = (ca.n_states + n_union) * q_pad
    packed = kernel == "packed"
    if packed:
        frontier = rng.integers(0, 2**32, (n_rows, v_pad), dtype=np.uint64).astype(np.uint32)
        frontier &= rng.random((n_rows, v_pad)) < 0.05
    else:
        frontier = (rng.random((n_rows, v_pad)) < 0.05).astype(np.float32)
    level = packed_level_blocks if packed else fused_level_blocks
    for tiles, sched in _schedules(path, tiny, ca):
        assert tiles.dtype == jnp.uint32 and tiles.shape[1:] == (tile_words(KB), KB)
        got = level(jnp.asarray(frontier), tiles, *(jnp.asarray(a) for a in sched), KB, q_pad,
                    n_out_rows=ca.n_states * q_pad)
        want = _dense_level(frontier, unpack_tiles(tiles, KB), sched, ca.n_states * q_pad,
                            q_pad, packed)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("tile_dtype", ["uint32", "f32"])
@pytest.mark.parametrize("kernel", ["packed", "fused"])
def test_level_kernel_bytes_count_the_blockspec_block(tiny, tile_dtype, kernel):
    """``kernel_bytes`` counts, per tile fetch, the bytes of the block the
    kernel's BlockSpec copies: read off the traced ``pallas_call``."""
    ca = paa.compile_query(PATH_QUERY, tiny)
    plan = fops.build_level_schedule(ca, fops.stage_graph(tiny, KB, tile_dtype=tile_dtype))
    sched = (plan.step, plan.src, plan.dst)
    n_rows = (ca.n_states + len(plan.union_members)) * plan.q_pad
    dtype = jnp.uint32 if kernel == "packed" else jnp.float32
    level = packed_level_blocks if kernel == "packed" else fused_level_blocks
    frontier = jnp.zeros((n_rows, plan.v_pad), dtype)
    jaxpr = jax.make_jaxpr(
        lambda f, t, *s: level(f, t, *s, KB, plan.q_pad, n_out_rows=ca.n_states * plan.q_pad)
    )(frontier, plan.tiles, *sched)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    tile_mapping = call.params["grid_mapping"].block_mappings[1]
    block = int(np.prod([b.block_size for b in tile_mapping.block_shape]))
    block_bytes = block * plan.tiles.dtype.itemsize
    want = tile_words(KB) * KB * 4 if tile_dtype == "uint32" else KB * KB * 4
    assert block_bytes == want
    c = unpack_schedule(*sched)
    arrays = [c[k] for k in ("tile_ids", "f_rows", "f_cols", "o_rows", "o_cols")]
    assert plan.kernel_bytes == fops.level_kernel_bytes(
        *arrays, plan.q_pad, KB, block_bytes) > 0


# ---------------------------------------------------------------------------
# a Stage-A snapshot in the old layout is rebuilt, never read
# ---------------------------------------------------------------------------


def _old_layout(tiles: np.ndarray, block_size: int) -> np.ndarray:
    """The layout before the lane-dense store: (n, B, ceil(B/32)) words,
    dst bits packed along the minor axis."""
    dense = unpack_tiles(tiles, block_size) > 0
    n, w = dense.shape[0], tile_words(block_size)
    bits = np.zeros((n, block_size, w * 32), bool)
    bits[:, :, :block_size] = dense
    shifts = np.arange(32, dtype=np.uint32)
    words = bits.reshape(n, block_size, w, 32).astype(np.uint32) << shifts
    return np.bitwise_or.reduce(words, axis=3)


@pytest.mark.parametrize("version", ["v1", "v2_old_tiles"])
def test_old_layout_snapshot_is_rebuilt(tiny, tmp_path, version):
    placement = distribute(tiny, n_sites=4, replication_rate=0.3, seed=2)
    store = GraphPlanStore()
    fresh = store.staged_graph(placement.graph, KB, tile_dtype="uint32")
    store.staged_sharded(placement, KB, tile_dtype="uint32")
    path = os.path.join(tmp_path, "stage_a.pkl")
    persist.save_stage_a(store, placement, path)
    with open(path, "rb") as f:
        blob = pickle.load(f)
    for _, key, payload in blob["entries"]:
        if key[0] == "staged_graph":
            payload["tiles"] = _old_layout(payload["tiles"], KB)
        else:
            payload["site_tiles"] = [_old_layout(t, KB) for t in payload["site_tiles"]]
    if version == "v1":
        blob["format_version"] = 1
    with open(path, "wb") as f:
        pickle.dump(blob, f)
    restored = GraphPlanStore()
    assert not persist.load_stage_a(restored, placement, path)
    fops.reset_build_counters()
    rebuilt = restored.staged_graph(placement.graph, KB, tile_dtype="uint32")
    assert fops.BUILD_COUNTERS["stage_graph"] == 1  # packed anew from the edges
    np.testing.assert_array_equal(np.asarray(rebuilt.tiles), np.asarray(fresh.tiles))
