"""jit'd wrappers: PAA levels and fixpoints on the Pallas frontier kernels.

Compilation is **two-stage** (the paper's §4 planner separation between
what depends on the data distribution and what depends on the query):

* **Stage A — graph-dependent, automaton-independent.**
  ``make_blocked_graph`` packs every label's adjacency into block-sparse
  tiles; :func:`stage_graph` concatenates all label stores — plus one
  *any-label union store* per direction, so a wildcard transition costs
  one tile list instead of |labels| — into ONE device tile tensor with
  per-(direction, label) offset tables.  :func:`stage_sharded_graph`
  does the same per site, keeping each site's slab at its own natural
  size; :func:`bucket_staged_sites` then groups the per-site slabs into
  a small set of power-of-two tile-count *shape buckets* (stacked per
  bucket for ``shard_map``/``vmap`` dispatch).  Built once per (graph,
  block_size) — shared by every automaton signature (see
  :class:`repro.core.plans.GraphPlanStore`, which caches Stage A per
  shape bucket).

* **Stage B — automaton-dependent, cheap.**
  :func:`build_level_schedule` / :func:`build_sharded_level_schedule`
  only compute grid ordering and the scalar-prefetch id arrays over the
  Stage-A offsets — zero tile packing, zero tile-tensor transfers; the
  returned plans *alias* the staged tiles.  Transitions that share
  (dst_state, direction, label) fuse into ONE pass over a *fan-in union
  row* (``Σ_src f[src] @ A == (Σ_src f[src]) @ A`` under saturating
  counts); the virtual union rows are appended to the frontier operand
  by :func:`extend_frontier` and recorded on the plan as
  ``union_members``.

Four execution paths share the staged tiles:

* **Fused (default)** — ``build_level_plan`` schedules every fan-in
  transition group's tile list of a compiled automaton into one grid
  sorted by (dst_state, block_col); ``expand_level_fused`` runs a whole
  BFS level as ONE ``pallas_call`` and ``reach_fixpoint`` wraps it in a
  device-resident ``lax.while_loop`` (no host syncs between levels).
  The 8-row f32 tile minimum carries up to ``QPAD`` stacked queries, so
  ``multi_query_reach`` answers 8 start masks for the price of one.

* **Bitpacked lanes** — the same Stage-B plan drives
  ``packed_level_blocks``: frontier rows become uint32 lane *words*
  (lane q = word row ``q // 32``, bit ``q % 32``), so the 8-row tile
  minimum carries ``QPACK = 256`` query lanes per state at 1/32 the
  frontier HBM of f32 stacking.  ``reach_fixpoint_packed`` converges on
  integer deltas and ``multi_query_reach_packed`` chunks queries at 256
  — bit-exact vs the f32 path on the boolean semiring.

* **Site-sharded fused** — ``build_sharded_level_plan`` builds one such
  schedule per *site* from that site's own edge partition and pads each
  only up to its shape bucket's power-of-two grid length (padding steps
  are ``valids=0`` predicates, skipped in-kernel — no tile pass);
  ``repro.core.strategies`` dispatches each bucket's stacked sites as
  one ``vmap``-ped fused call under ``shard_map``
  (``backend="frontier_kernel_sharded"``) — the paper's distribution
  model on the fused kernel path.

* **Per-transition baseline** — ``expand_level`` issues one Pallas call
  per transition × label entry with a host-side merge, and
  ``multi_source_reach_baseline`` loops levels on the host.  Kept as the
  dispatch-count/perf baseline (see ``benchmarks/frontier_level.py``).

Every wrapper takes ``interpret=None``, resolved by
:func:`repro.kernels.frontier.frontier.resolve_interpret`: the Pallas
interpreter on CPU (the validation mode), the compiled kernels on a TPU.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.automaton import FWD, INV, CompiledAutomaton
from repro.core.witness import INF_LEVEL
from repro.graph.structure import LabeledGraph
from repro.kernels.frontier.frontier import (
    frontier_step_blocks,
    fused_level_blocks,
    pack_schedule,
    packed_level_blocks,
    resolve_interpret,
    tile_block_shape,
)
from repro.kernels.frontier.ref import (
    TILE_DTYPES,
    pack_blocks,
    pack_blocks_chunked,
    tile_shape,
)

# f32 sublane minimum: the row-tile rows one query would waste, used to
# stack up to QPAD independent queries' frontiers per automaton state.
QPAD = 8

# Bitpacked lane capacity: the packed backend keeps the same QPAD word
# rows per state but each row is uint32 lane *words*, so one tile-height
# frontier block carries QPAD × 32 = 256 independent query lanes.  Lane
# q lives in word row ``q // 32``, bit ``q % 32``.
QPACK = QPAD * 32

# offset-table key for the any-label union store (wildcard transitions);
# real label ids are >= 0 so the key space is disjoint.
ANY_LABEL = -1

# smallest power-of-two shape class for bucketed sharded grids: buckets
# never round below this, so near-empty sites share one tiny class
# instead of fragmenting into one bucket each.
BUCKET_FLOOR = 8

# Build-path instrumentation: every Stage-A packing/staging op and every
# Stage-B schedule construction bumps a counter, so tests and
# ``benchmarks/plan_store.py`` can assert that warm executor builds pack
# ZERO tiles (the two-stage compilation contract).
BUILD_COUNTERS: collections.Counter = collections.Counter()


def reset_build_counters() -> None:
    BUILD_COUNTERS.clear()


def shape_class(n: int, floor: int = BUCKET_FLOOR) -> int:
    """The power-of-two shape bucket ``n`` rounds up into (≥ ``floor``)."""
    n = max(int(n), 1)
    return max(floor, 1 << (n - 1).bit_length())


def device_bytes(array) -> int:
    """Bytes a device array takes as allocated, in the device's own
    layout (tile padding included); a host array's ``nbytes``."""
    size = getattr(array, "on_device_size_in_bytes", None)
    return int(size()) if size is not None else int(np.asarray(array).nbytes)


@dataclasses.dataclass
class BlockedGraph:
    n_nodes: int
    v_pad: int
    block_size: int
    # per label id: forward tiles + transposed (inverse) tiles
    fwd: dict[int, tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]
    inv: dict[int, tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]


def make_blocked_graph(graph: LabeledGraph, block_size: int = 128) -> BlockedGraph:
    BUILD_COUNTERS["make_blocked_graph"] += 1
    fwd, inv = {}, {}
    for lid in range(graph.n_labels):
        src, dst = graph.edges_with_label(lid)
        if len(src) == 0:
            continue
        BUILD_COUNTERS["pack_blocks"] += 2
        t, r, c, v_pad = pack_blocks(src, dst, graph.n_nodes, block_size)
        fwd[lid] = (jnp.asarray(t), jnp.asarray(r), jnp.asarray(c))
        t, r, c, _ = pack_blocks(dst, src, graph.n_nodes, block_size)
        inv[lid] = (jnp.asarray(t), jnp.asarray(r), jnp.asarray(c))
    v_pad = -(-graph.n_nodes // block_size) * block_size
    return BlockedGraph(graph.n_nodes, v_pad, block_size, fwd, inv)


# ---------------------------------------------------------------------------
# Stage A: staged tile tensors (graph-dependent, automaton-independent)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StagedGraph:
    """Stage-A artifact: every label store's tiles in ONE device tensor.

    ``tiles[0]`` is the all-zero cover tile; ``offsets[(direction,
    label_id)] = (base, block_rows, block_cols)`` says where that label
    store's tiles start and which (row, col) block each occupies.  The
    ``(direction, ANY_LABEL)`` entries are the any-label union stores
    (the saturated OR of every label's adjacency per direction) that
    ground wildcard transitions in one tile list.  Automaton-independent:
    any number of Stage-B schedules (:func:`build_level_schedule`) index
    into one staged tensor without re-packing or re-transferring tiles."""

    n_nodes: int
    v_pad: int
    block_size: int
    tiles: jnp.ndarray  # (1 + sum nnz, *tile_shape); index 0 = zero cover tile
    offsets: dict[tuple[int, int], tuple[int, np.ndarray, np.ndarray]]
    # total edge-list slices consumed by chunked Stage-A packing (0 when
    # the one-shot path packed every label store in one pass)
    staging_chunks: int = 0
    # "f32" (dense 0/1 tiles, every semiring) or "uint32" (src axis
    # bitpacked into ceil(B/32) lane-dense word rows — boolean semiring
    # only, at 1/32 the staged bytes); see ``ref.pack_blocks``'s
    # tile_dtype path
    tile_dtype: str = "f32"

    @property
    def n_tiles(self) -> int:
        """Staged tiles, the zero cover tile included."""
        return int(self.tiles.shape[0])

    @property
    def tile_store_bytes(self) -> int:
        """Total staged tile-tensor bytes (cover tile included): the raw
        bytes, tiles times one tile's bytes."""
        return int(self.tiles.nbytes)

    @property
    def store_bytes(self) -> int:
        """Device bytes of the staged tile tensor as allocated, layout
        padding included (:func:`device_bytes`)."""
        return device_bytes(self.tiles)

    def slab_bytes(self) -> dict[tuple[int, int], int]:
        """Per-(direction, label) staged bytes — each slab's tile count
        times the per-tile footprint of this store's dtype.  Derived
        from the offset tables, so it costs nothing to carry."""
        per_tile = self.tile_store_bytes // max(self.n_tiles, 1)
        return {k: len(rows) * per_tile for k, (_, rows, _) in self.offsets.items()}


def _union_store(
    stores: dict[tuple[int, int], tuple[np.ndarray, np.ndarray, np.ndarray]],
    direction: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """The any-label union store of one direction: the block-sparse
    saturated OR of every label store's tiles (an edge with any label is
    an edge), so a wildcard grounds to ONE tile list instead of |labels|.

    Bitpacked uint32 stores union with bitwise OR — ``np.maximum`` on
    word values is NOT the set union of their bits."""
    acc: dict[tuple[int, int], np.ndarray] = {}
    packed = False
    for (d, lid), (t, r, c) in stores.items():
        if d != direction or lid < 0:
            continue
        packed = t.dtype == np.uint32
        combine = np.bitwise_or if packed else np.maximum
        for j in range(t.shape[0]):
            key = (int(r[j]), int(c[j]))
            if key in acc:
                acc[key] = combine(acc[key], t[j])
            else:
                acc[key] = np.asarray(t[j]).copy()
    if not acc:
        return None
    keys = sorted(acc, key=lambda rc: (rc[1], rc[0]))  # pack_blocks col order
    stack = np.stack([acc[k] for k in keys])
    tiles = stack if packed else np.minimum(stack, 1.0).astype(np.float32)
    rows = np.asarray([k[0] for k in keys], np.int32)
    cols = np.asarray([k[1] for k in keys], np.int32)
    return tiles, rows, cols


def _label_tile_lists(
    source: LabeledGraph | BlockedGraph,
    block_size: int,
    chunk_edges: int | None = None,
    tile_dtype: str = "f32",
) -> tuple[
    int, int, dict[tuple[int, int], tuple[np.ndarray, np.ndarray, np.ndarray]], int
]:
    """Host tile lists per (direction, label) — plus the two
    ``(direction, ANY_LABEL)`` union stores — from a raw graph (packing
    directly to numpy, no per-label device arrays) or an existing
    :class:`BlockedGraph` (pulling its tiles back to host once).

    With ``chunk_edges`` set, each label store streams through
    :func:`pack_blocks_chunked` (byte-identical tiles, peak transient
    host memory bounded by the chunk size); the last return value counts
    the edge-list slices consumed (0 on the one-shot path)."""
    if tile_dtype not in TILE_DTYPES:
        raise ValueError(f"tile_dtype must be one of {TILE_DTYPES}, got {tile_dtype!r}")
    staging_chunks = 0
    if isinstance(source, BlockedGraph):
        if tile_dtype != "f32":
            raise ValueError(
                "a BlockedGraph carries pre-packed f32 tiles; stage from the "
                "LabeledGraph to get a tile_dtype='uint32' store"
            )
        stores = {}
        for direction, store in ((FWD, source.fwd), (INV, source.inv)):
            for lid, (t, r, c) in store.items():
                stores[(direction, lid)] = (np.asarray(t), np.asarray(r), np.asarray(c))
        n_nodes, v_pad = source.n_nodes, source.v_pad
    else:
        g = source
        stores = {}
        for lid in range(g.n_labels):
            src, dst = g.edges_with_label(lid)
            if len(src) == 0:
                continue
            BUILD_COUNTERS["pack_blocks"] += 2
            if chunk_edges is None:
                t, r, c, _ = pack_blocks(src, dst, g.n_nodes, block_size, tile_dtype)
                stores[(FWD, lid)] = (t, r, c)
                t, r, c, _ = pack_blocks(dst, src, g.n_nodes, block_size, tile_dtype)
                stores[(INV, lid)] = (t, r, c)
            else:
                t, r, c, _, nc = pack_blocks_chunked(
                    src, dst, g.n_nodes, block_size, chunk_edges, tile_dtype
                )
                stores[(FWD, lid)] = (t, r, c)
                staging_chunks += nc
                t, r, c, _, nc = pack_blocks_chunked(
                    dst, src, g.n_nodes, block_size, chunk_edges, tile_dtype
                )
                stores[(INV, lid)] = (t, r, c)
                staging_chunks += nc
        n_nodes = g.n_nodes
        v_pad = -(-g.n_nodes // block_size) * block_size
    for direction in (FWD, INV):
        u = _union_store(stores, direction)
        if u is not None:
            stores[(direction, ANY_LABEL)] = u
    BUILD_COUNTERS["staging_chunks"] += staging_chunks
    return n_nodes, v_pad, stores, staging_chunks


def _concat_stores(
    stores: dict[tuple[int, int], tuple[np.ndarray, np.ndarray, np.ndarray]],
    block_size: int,
    tile_dtype: str = "f32",
) -> tuple[np.ndarray, dict[tuple[int, int], tuple[int, np.ndarray, np.ndarray]]]:
    """Concatenate label stores behind the zero cover tile (index 0) and
    record each store's base offset + block coordinates — the staging
    layout shared by the global and per-site Stage-A builders."""
    dtype = np.uint32 if tile_dtype == "uint32" else np.float32
    tile_arrays = [np.zeros((1, *tile_shape(block_size, tile_dtype)), dtype)]
    offsets: dict[tuple[int, int], tuple[int, np.ndarray, np.ndarray]] = {}
    off = 1
    for key in sorted(stores):
        t, r, c = stores[key]
        tile_arrays.append(t)
        offsets[key] = (off, r, c)
        off += int(t.shape[0])
    return np.concatenate(tile_arrays, axis=0), offsets


def stage_graph(
    source: LabeledGraph | BlockedGraph,
    block_size: int = 128,
    chunk_edges: int | None = None,
    tile_dtype: str = "f32",
) -> StagedGraph:
    """Stage A for the global fused backend: pack (if needed) and
    concatenate every label's tiles — plus the per-direction any-label
    union stores — into one device tensor + offsets.

    ``chunk_edges`` streams the per-label packing in edge slices
    (:func:`pack_blocks_chunked`): the staged tensor is byte-identical
    to the one-shot path, but the transient per-edge key/inverse arrays
    never exceed one chunk — the out-of-core knob for graphs whose edge
    lists dwarf host RAM.  ``tile_dtype="uint32"`` stages the bitpacked
    store (1/32 the tensor bytes, boolean semiring only)."""
    BUILD_COUNTERS["stage_graph"] += 1
    n_nodes, v_pad, stores, staging_chunks = _label_tile_lists(
        source, block_size, chunk_edges, tile_dtype
    )
    tiles, offsets = _concat_stores(stores, block_size, tile_dtype)
    return StagedGraph(
        n_nodes=n_nodes,
        v_pad=v_pad,
        block_size=block_size,
        tiles=jnp.asarray(tiles),
        offsets=offsets,
        staging_chunks=staging_chunks,
        tile_dtype=tile_dtype,
    )


def pack_label_store(
    graph: LabeledGraph,
    direction: int,
    label_id: int,
    block_size: int,
    chunk_edges: int | None = None,
    tile_dtype: str = "f32",
) -> tuple[tuple[np.ndarray, np.ndarray, np.ndarray] | None, int]:
    """Pack ONE (direction, label) slab straight from the edge stream —
    the out-of-core tile store's build/rebuild unit (see
    :meth:`repro.core.plans.GraphPlanStore.staged_graph`).

    ``label_id == ANY_LABEL`` packs every edge of the direction; that is
    byte-identical to the ``_union_store`` full staging produces, because
    both sort blocks by (col, row) and store binary presence — an edge
    with any label is an edge.  Returns ``(slab | None, n_chunks)``;
    ``None`` when the graph has no matching edges (full staging omits
    the offset key for such labels too)."""
    if label_id == ANY_LABEL:
        src, dst = graph.src, graph.dst
    else:
        src, dst = graph.edges_with_label(label_id)
    if direction == INV:
        src, dst = dst, src
    if len(src) == 0:
        return None, 0
    BUILD_COUNTERS["pack_blocks"] += 1
    if chunk_edges is None:
        t, r, c, _ = pack_blocks(src, dst, graph.n_nodes, block_size, tile_dtype)
        return (t, r, c), 0
    t, r, c, _, nc = pack_blocks_chunked(
        src, dst, graph.n_nodes, block_size, chunk_edges, tile_dtype
    )
    BUILD_COUNTERS["staging_chunks"] += nc
    return (t, r, c), nc


def assemble_staged(
    stores: dict[tuple[int, int], tuple[np.ndarray, np.ndarray, np.ndarray]],
    n_nodes: int,
    block_size: int,
    tile_dtype: str = "f32",
    staging_chunks: int = 0,
) -> StagedGraph:
    """Build a :class:`StagedGraph` from already-packed host slabs — the
    label-subset assembly path of the byte-budgeted tile store.  Packs
    nothing (slabs come from :func:`pack_label_store` or a spill file);
    a schedule built against the subset sees exactly the offset keys in
    ``stores``, so the requested keys must cover the automaton's
    :func:`required_offset_keys`."""
    tiles, offsets = _concat_stores(stores, block_size, tile_dtype)
    v_pad = -(-n_nodes // block_size) * block_size
    return StagedGraph(
        n_nodes=n_nodes,
        v_pad=v_pad,
        block_size=block_size,
        tiles=jnp.asarray(tiles),
        offsets=offsets,
        staging_chunks=staging_chunks,
        tile_dtype=tile_dtype,
    )


@dataclasses.dataclass
class StagedShardedGraph:
    """Stage A for the site-sharded backend: per-site staged tile slabs,
    each at its *own natural* tile count (no cross-site padding here —
    shape bucketing happens in :func:`bucket_staged_sites`).  Slabs stay
    on host; the device transfer happens once per shape bucket when the
    bucket stacks are built.  Per-site offset tables index into that
    site's slab; Stage-B schedules (:func:`build_sharded_level_schedule`)
    share one staging across every automaton signature."""

    n_sites: int
    n_nodes: int
    v_pad: int
    block_size: int
    site_tiles: tuple[np.ndarray, ...]  # per site: (n_tiles_s, *tile_shape)
    site_offsets: tuple[dict[tuple[int, int], tuple[int, np.ndarray, np.ndarray]], ...]
    tile_dtype: str = "f32"  # see StagedGraph.tile_dtype

    @property
    def site_n_tiles(self) -> tuple[int, ...]:
        return tuple(int(t.shape[0]) for t in self.site_tiles)

    @property
    def tile_store_bytes(self) -> int:
        """Total staged bytes across every site slab."""
        return int(sum(np.asarray(t).nbytes for t in self.site_tiles))


def stage_sharded_graph(
    site_graphs: list[LabeledGraph], block_size: int = 128, tile_dtype: str = "f32"
) -> StagedShardedGraph:
    """Stage A per site: each site's tile lists come from *its own* edge
    partition (replication included), kept at the site's natural size —
    padding only happens later, up to the site's power-of-two shape
    bucket (:func:`bucket_staged_sites`), never up to the global max.

    Every site graph must share ``n_nodes`` (the global node id space) so
    all sites agree on ``v_pad`` and block indexing; a site holding zero
    edges (or none for some label) contributes only the zero cover tile.
    """
    if not site_graphs:
        raise ValueError("need at least one site graph")
    n_nodes = site_graphs[0].n_nodes
    if any(g.n_nodes != n_nodes for g in site_graphs):
        raise ValueError("site graphs must share the global node id space")
    BUILD_COUNTERS["stage_sharded_graph"] += 1
    site_tiles, site_offsets = [], []
    for g in site_graphs:
        _, _, stores, _ = _label_tile_lists(g, block_size, tile_dtype=tile_dtype)
        t, offsets = _concat_stores(stores, block_size, tile_dtype)
        site_tiles.append(t)
        site_offsets.append(offsets)
    v_pad = -(-n_nodes // block_size) * block_size
    return StagedShardedGraph(
        n_sites=len(site_graphs),
        n_nodes=n_nodes,
        v_pad=v_pad,
        block_size=block_size,
        site_tiles=tuple(site_tiles),
        site_offsets=tuple(site_offsets),
        tile_dtype=tile_dtype,
    )


def merge_staged_sites(
    staged: StagedShardedGraph, n_groups: int
) -> StagedShardedGraph:
    """Merge blocks of co-located sites into device-granular staging.

    Under ``shard_map`` device ``d`` holds sites ``[d·k, (d+1)·k)``
    (``k = n_sites / n_groups``); expansion-wise those sites' edges can
    share ONE fused grid over their *deduplicated union* tiles — the
    boolean-semiring level is identical on the union, co-located
    replicas dedup for free, and the per-site cover steps collapse to
    one set per device.  Per-site identity is untouched: the §4.2
    meters keep their per-site degree vectors and the cross-device
    exchange still moves only site-held discoveries.  Returns ``staged``
    itself when ``k == 1`` (nothing to merge).  Host-side tile max — no
    repacking from edges."""
    if staged.n_sites % n_groups:
        raise ValueError(
            f"n_sites={staged.n_sites} must be divisible by n_groups={n_groups}"
        )
    k = staged.n_sites // n_groups
    if k == 1:
        return staged
    BUILD_COUNTERS["merge_staged_sites"] += 1
    # uint32 word tiles union with bitwise OR (max on word values is not
    # the union of their bit sets); f32 0/1 tiles keep the max form
    combine = np.bitwise_or if staged.tile_dtype == "uint32" else np.maximum
    site_tiles, site_offsets = [], []
    for d in range(n_groups):
        acc: dict[tuple[int, int], dict[tuple[int, int], np.ndarray]] = {}
        for s in range(d * k, (d + 1) * k):
            slab = staged.site_tiles[s]
            for key, (base, rows, cols) in staged.site_offsets[s].items():
                cur = acc.setdefault(key, {})
                for j in range(len(rows)):
                    rc = (int(rows[j]), int(cols[j]))
                    t = slab[base + j]
                    cur[rc] = (
                        combine(cur[rc], t) if rc in cur else np.asarray(t).copy()
                    )
        stores = {}
        for key, tilemap in acc.items():
            rcs = sorted(tilemap, key=lambda rc: (rc[1], rc[0]))  # pack_blocks order
            stores[key] = (
                np.stack([tilemap[rc] for rc in rcs]),
                np.asarray([rc[0] for rc in rcs], np.int32),
                np.asarray([rc[1] for rc in rcs], np.int32),
            )
        t, offsets = _concat_stores(stores, staged.block_size, staged.tile_dtype)
        site_tiles.append(t)
        site_offsets.append(offsets)
    return StagedShardedGraph(
        n_sites=n_groups,
        n_nodes=staged.n_nodes,
        v_pad=staged.v_pad,
        block_size=staged.block_size,
        site_tiles=tuple(site_tiles),
        site_offsets=tuple(site_offsets),
        tile_dtype=staged.tile_dtype,
    )


@dataclasses.dataclass
class TileBucket:
    """One power-of-two tile shape class of :func:`bucket_staged_sites`.

    ``tiles`` stacks the member sites' slabs (zero-padded up to
    ``n_tiles``) in shard_map row order: row ``d * len(slots) + j`` is
    the site at slot ``slots[j]`` on device ``d``, so sharding the
    leading dim over the site axes hands every device exactly its own
    ``len(slots)`` rows — ready for one ``vmap``-ped fused call."""

    n_tiles: int  # power-of-two padded per-site tile count
    slots: tuple[int, ...]  # local site indices (uniform across devices)
    sites: tuple[int, ...]  # global site ids, row-by-row (device-major)
    tiles: jnp.ndarray  # (axis_size * len(slots), n_tiles, *tile_shape)


@dataclasses.dataclass
class ShardedTileBuckets:
    """Stage-A shape buckets: the staged per-site slabs grouped into a
    small set of power-of-two tile-count classes.

    Bucketing is by *slot* (a site's local index within its device's
    block of ``n_sites / axis_size`` sites): under ``shard_map`` every
    device traces ONE program, so per-site shape freedom exists only
    across slots, and a slot's class is the power-of-two roundup of the
    max tile count among the sites sharing it across devices.  At
    ``axis_size=1`` (one device) slots are sites and each site lands in
    its natural class.  Assignment is deterministic: ``bucket_id`` is a
    pure function of (per-site tile counts, axis_size, floor)."""

    axis_size: int
    s_local: int
    floor: int
    buckets: tuple[TileBucket, ...]

    @property
    def bucket_id(self) -> tuple:
        """Deterministic shape-bucket descriptor — joins the executor
        cache's graph key (see ``repro.serve.plancache``)."""
        return (
            self.axis_size,
            self.floor,
            tuple((b.n_tiles, b.slots) for b in self.buckets),
        )


def bucket_staged_sites(
    staged: StagedShardedGraph,
    axis_size: int = 1,
    floor: int = BUCKET_FLOOR,
    sharding: jax.sharding.Sharding | None = None,
) -> ShardedTileBuckets:
    """Group the staged per-site slabs into power-of-two tile shape
    buckets and stack each bucket's slabs on device (Stage A, cached per
    shape bucket by :class:`repro.core.plans.GraphPlanStore`).
    ``sharding`` places each stack, typically split over the mesh's site
    axes so that every device holds only its own sites' rows (default:
    the default device).

    Quantization exists to let several members share ONE jitted program
    (and, across devices, one SPMD shape) — a bucket that ends up with a
    single member row has nothing to unify, so it keeps its natural tile
    count instead of paying the power-of-two roundup."""
    if staged.n_sites % axis_size:
        raise ValueError(
            f"n_sites={staged.n_sites} must be divisible by the site-axis "
            f"size {axis_size} (sites are blocked over the site axes)"
        )
    BUILD_COUNTERS["bucket_staged_sites"] += 1
    s_local = staged.n_sites // axis_size
    n_tiles = staged.site_n_tiles
    slot_class = {
        sl: shape_class(
            max(n_tiles[d * s_local + sl] for d in range(axis_size)), floor
        )
        for sl in range(s_local)
    }
    by_class: dict[int, list[int]] = {}
    for sl in range(s_local):
        by_class.setdefault(slot_class[sl], []).append(sl)
    b = staged.block_size
    buckets = []
    for cls in sorted(by_class):
        slots = tuple(sorted(by_class[cls]))
        sites = tuple(
            d * s_local + sl for d in range(axis_size) for sl in slots
        )
        if len(sites) == 1:  # nothing to unify: natural shape, no roundup
            cls = n_tiles[sites[0]]
        dtype = np.uint32 if staged.tile_dtype == "uint32" else np.float32
        stack = np.zeros((len(sites), cls, *tile_shape(b, staged.tile_dtype)), dtype)
        for row, s in enumerate(sites):
            stack[row, : n_tiles[s]] = staged.site_tiles[s]
        buckets.append(
            TileBucket(
                n_tiles=cls, slots=slots, sites=sites,
                tiles=jax.device_put(stack, sharding),
            )
        )
    return ShardedTileBuckets(
        axis_size=axis_size, s_local=s_local, floor=floor, buckets=tuple(buckets)
    )


# ---------------------------------------------------------------------------
# Fan-in union rows (shared by the global and sharded Stage-B schedules)
# ---------------------------------------------------------------------------


def fanin_frontier_rows(
    ca: CompiledAutomaton,
) -> tuple[dict[tuple[int, int, int], int], tuple[tuple[int, ...], ...]]:
    """Fan-in transition grouping: transitions sharing (dst_state,
    direction, label) read ONE frontier row, because under saturating
    counts ``Σ_src f[src] @ A == (Σ_src f[src]) @ A``.

    Returns ``(frow_map, union_members)``: ``frow_map[(dst, direction,
    label_id)]`` is the frontier row-block the group reads — the single
    source state, or a virtual union row ``n_states + u`` whose member
    states are ``union_members[u]``.  Identical source sets share one
    union row across groups.  Pure function of the automaton, so every
    site of a sharded plan agrees on the extended frontier layout."""
    groups: dict[tuple[int, int, int], set[int]] = {}
    for t in ca.transitions:
        groups.setdefault((t.dst, t.direction, t.label_id), set()).add(t.src)
    frow_map: dict[tuple[int, int, int], int] = {}
    union_index: dict[tuple[int, ...], int] = {}
    union_members: list[tuple[int, ...]] = []
    for key in sorted(groups):
        srcs = tuple(sorted(groups[key]))
        if len(srcs) == 1:
            frow_map[key] = srcs[0]
        else:
            if srcs not in union_index:
                union_index[srcs] = len(union_members)
                union_members.append(srcs)
            frow_map[key] = ca.n_states + union_index[srcs]
    return frow_map, tuple(union_members)


def extend_frontier(
    frontier: jnp.ndarray,  # (n_states * q_pad, v_pad) f32 0/1
    union_members: tuple[tuple[int, ...], ...],
    n_states: int,
    q_pad: int,
) -> jnp.ndarray:
    """Append one virtual row-block per fan-in source union: row-block
    ``n_states + u`` is the elementwise OR (max on {0,1}) of the member
    states' frontiers.  Cheap jnp ops outside the kernel — the fused
    grid then reads each union ONCE per tile instead of once per member."""
    if not union_members:
        return frontier
    v_pad = frontier.shape[-1]
    fr3 = frontier.reshape(n_states, q_pad, v_pad)
    ext = [fr3] + [
        fr3[jnp.asarray(m, jnp.int32)].max(axis=0, keepdims=True)
        for m in union_members
    ]
    return jnp.concatenate(ext, axis=0).reshape(
        (n_states + len(union_members)) * q_pad, v_pad
    )


def extend_frontier_packed(
    frontier: jnp.ndarray,  # (n_states * q_pad, v_pad) uint32 lane words
    union_members: tuple[tuple[int, ...], ...],
    n_states: int,
    q_pad: int,
) -> jnp.ndarray:
    """:func:`extend_frontier` on bitpacked lane words: the fan-in union
    of member states is the bitwise OR of their word rows (each query
    lane unions independently in its own bit)."""
    if not union_members:
        return frontier
    v_pad = frontier.shape[-1]
    fr3 = frontier.reshape(n_states, q_pad, v_pad)
    ext = [fr3] + [
        jax.lax.reduce(
            fr3[jnp.asarray(m, jnp.int32)],
            jnp.uint32(0),
            jax.lax.bitwise_or,
            (0,),
        )[None]
        for m in union_members
    ]
    return jnp.concatenate(ext, axis=0).reshape(
        (n_states + len(union_members)) * q_pad, v_pad
    )


def extend_frontier_sum(
    frontier: jnp.ndarray,  # (n_states * q_pad, v_pad) f32 run counts
    union_members: tuple[tuple[int, ...], ...],
    n_states: int,
    q_pad: int,
) -> jnp.ndarray:
    """:func:`extend_frontier` on the counting semiring: fan-in union
    rows must be the SUM of the member states' count rows, not the max —
    ``Σ_src f[src] @ A`` is literal there (no saturation to hide under).
    Used by :func:`count_paths_bounded`; the boolean fixpoints keep the
    max form."""
    if not union_members:
        return frontier
    v_pad = frontier.shape[-1]
    fr3 = frontier.reshape(n_states, q_pad, v_pad)
    ext = [fr3] + [
        fr3[jnp.asarray(m, jnp.int32)].sum(axis=0, keepdims=True)
        for m in union_members
    ]
    return jnp.concatenate(ext, axis=0).reshape(
        (n_states + len(union_members)) * q_pad, v_pad
    )


# ---------------------------------------------------------------------------
# Fused level plan: all transitions of a level as one grid
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FusedLevelPlan:
    """Host-built schedule for :func:`fused_level_blocks`.

    One grid step per (fan-in transition group, label, nonzero tile)
    triple, plus one zero-tile cover step per output block no real step
    writes (so every output block is initialized).  Steps are sorted by
    (dst_state, block_col) — the output-revisiting order — and packed
    once, here on the host, into the three step words the kernel
    prefetches: each step's ``first`` bit marks an output block's first
    step for the in-kernel zero-init, and its ``valid`` bit marks the
    steps that carry a real tile (cover steps skip the tile product
    in-kernel).  ``union_members`` lists the fan-in union rows a step's
    frontier row may address past ``n_states``; callers extend the
    frontier with :func:`extend_frontier` first.
    """

    n_states: int
    n_nodes: int
    v_pad: int
    block_size: int
    q_pad: int
    n_real_steps: int  # grid steps carrying a real tile (excludes covers)
    union_members: tuple[tuple[int, ...], ...]
    tiles: jnp.ndarray  # (n_tiles, *tile_shape); index 0 is the all-zero cover tile
    # (n_steps,) int32 step words (frontier.pack_schedule): tile id,
    # valid, first; frontier row (src state or union row) and block; output
    # row (dst automaton state) and block
    step: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray
    # dtype of the aliased tile store ("f32" or "uint32") — the kernels
    # dispatch off the array dtype; the field gates the f32-only
    # semirings (witness levels, counting) at the wrapper layer
    tile_dtype: str = "f32"
    # HBM bytes one call of the level kernel moves (level_kernel_bytes)
    kernel_bytes: int = 0


def level_kernel_bytes(
    tile_ids: np.ndarray,
    f_rows: np.ndarray,
    f_cols: np.ndarray,
    o_rows: np.ndarray,
    o_cols: np.ndarray,
    q_pad: int,
    block_size: int,
    tile_block_bytes: int,
) -> int:
    """HBM bytes one call of the fused or packed level kernel moves for a
    Stage-B schedule (one BFS level).

    The kernel's pipeline copies a block in only where the block's index
    differs from the step before (and writes an output block back when
    the grid leaves it), so this counts, over the grid steps: the tile
    block (``tile_block_bytes``) where ``tile_ids`` changes, the
    ``(q_pad, block_size)`` frontier block where ``(f_rows, f_cols)``
    changes, the output block of the same size where ``(o_rows, o_cols)``
    changes, and the three int32 scalar-prefetch step words
    (``frontier.pack_schedule``).  Frontier and
    output elements are 4 bytes (f32 rows or uint32 lane words).  A
    cover step's zero tile counts like any other: the copy does not know
    that ``valids`` skips the product."""
    n = len(tile_ids)
    if n == 0:
        return 0

    def fetches(*cols) -> int:
        same = np.ones(n - 1, bool)
        for c in cols:
            c = np.asarray(c)
            same &= c[1:] == c[:-1]
        return 1 + int(np.count_nonzero(~same))

    row_block = q_pad * block_size * 4
    return (
        fetches(tile_ids) * tile_block_bytes
        + fetches(f_rows, f_cols) * row_block
        + fetches(o_rows, o_cols) * row_block
        + 3 * 4 * n
    )


def required_offset_keys(ca: CompiledAutomaton) -> tuple[tuple[int, int], ...]:
    """The (direction, label) slab keys a Stage-B schedule for ``ca``
    reads: real labels stay themselves, wildcard transitions ground to
    the per-direction ``ANY_LABEL`` union store.  This is the label
    subset an out-of-core Stage A must have resident to serve ``ca``
    (see ``repro.core.plans.GraphPlanStore``'s byte-budgeted store)."""
    keys = {
        (t.direction, t.label_id if t.label_id >= 0 else ANY_LABEL)
        for t in ca.transitions
    }
    return tuple(sorted(keys))


def _schedule_steps(
    ca: CompiledAutomaton,
    offsets: dict[tuple[int, int], tuple[int, np.ndarray, np.ndarray]],
    nb: int,
    frow_map: dict[tuple[int, int, int], int],
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Stage-B core: the sorted (orow, ocol, frow, fcol, tid) step table
    for one automaton over one staged offset map, plus ``firsts``,
    ``valids``, and the real-step count.  Pure host indexing — no tile
    packing.  Each fan-in group contributes one pass per tile of its
    label store (the any-label union store for wildcards); labels with
    empty stores contribute nothing."""
    steps: list[tuple[int, int, int, int, int]] = []  # (orow, ocol, frow, fcol, tid)
    for (dst, direction, label_id), frow in sorted(frow_map.items()):
        if label_id >= 0:
            lids = [label_id]
        elif (direction, ANY_LABEL) in offsets:
            lids = [ANY_LABEL]
        else:  # no union store staged (e.g. a BlockedGraph without one)
            lids = sorted(l for (d, l) in offsets if d == direction and l >= 0)
        for lid in lids:
            ent = offsets.get((direction, lid))
            if ent is None:
                continue  # empty label store: no edges, nothing to expand
            base, rows, cols = ent
            for j in range(len(rows)):
                steps.append((dst, int(cols[j]), frow, int(rows[j]), base + j))
    n_real = len(steps)

    covered = {(s[0], s[1]) for s in steps}
    for s_dst in range(ca.n_states):
        for cblk in range(nb):
            if (s_dst, cblk) not in covered:
                steps.append((s_dst, cblk, 0, 0, 0))  # zero tile: pure init

    steps.sort(key=lambda s: (s[0], s[1]))
    arr = np.asarray(steps, np.int32).reshape(len(steps), 5)
    firsts = np.ones(len(steps), np.int32)
    if len(steps) > 1:
        same = (arr[1:, 0] == arr[:-1, 0]) & (arr[1:, 1] == arr[:-1, 1])
        firsts[1:][same] = 0
    valids = (arr[:, 4] > 0).astype(np.int32)  # tile 0 = zero cover tile
    return arr, firsts, valids, n_real


def build_level_schedule(
    ca: CompiledAutomaton, staged: StagedGraph, q_pad: int = QPAD
) -> FusedLevelPlan:
    """Stage B: schedule one fused BFS level for ``ca`` over Stage-A
    artifacts.  Wildcard transitions ground to the any-label union store
    (one tile list); fan-in groups read one (possibly virtual) frontier
    row.  The returned plan *aliases* ``staged.tiles`` — zero tile
    packing, zero device transfers of tile data."""
    BUILD_COUNTERS["level_schedule"] += 1
    nb = staged.v_pad // staged.block_size
    frow_map, union_members = fanin_frontier_rows(ca)
    arr, firsts, valids, n_real = _schedule_steps(ca, staged.offsets, nb, frow_map)
    step, src, dst = pack_schedule(
        firsts, valids, arr[:, 4], arr[:, 2], arr[:, 3], arr[:, 0], arr[:, 1]
    )
    tiles = staged.tiles
    tile_block_bytes = int(np.prod(tile_block_shape(tiles))) * tiles.dtype.itemsize
    return FusedLevelPlan(
        n_states=ca.n_states,
        n_nodes=staged.n_nodes,
        v_pad=staged.v_pad,
        block_size=staged.block_size,
        q_pad=q_pad,
        n_real_steps=n_real,
        union_members=union_members,
        tiles=staged.tiles,
        step=jnp.asarray(step),
        src=jnp.asarray(src),
        dst=jnp.asarray(dst),
        tile_dtype=staged.tile_dtype,
        kernel_bytes=level_kernel_bytes(
            arr[:, 4], arr[:, 2], arr[:, 3], arr[:, 0], arr[:, 1],
            q_pad, staged.block_size, tile_block_bytes,
        ),
    )


def build_level_plan(
    ca: CompiledAutomaton,
    bg: BlockedGraph | StagedGraph,
    q_pad: int = QPAD,
) -> FusedLevelPlan:
    """One-shot wrapper: stage (Stage A) then schedule (Stage B).

    Pass a :class:`StagedGraph` (e.g. from
    :class:`repro.core.plans.GraphPlanStore`) to skip straight to Stage
    B; a :class:`BlockedGraph` is staged here — the pre-refactor
    single-stage behavior, kept for standalone/one-off callers."""
    staged = bg if isinstance(bg, StagedGraph) else stage_graph(bg, bg.block_size)
    return build_level_schedule(ca, staged, q_pad)


# ---------------------------------------------------------------------------
# Site-sharded level plan: shape-bucketed per-site fused grids
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlanBucket:
    """One shape bucket of a :class:`ShardedLevelPlan`: the member
    sites' schedules stacked (shard_map row order, see
    :class:`TileBucket`) and padded to the bucket's power-of-two grid
    length ``n_steps``.  Padding steps are ``first=0, valid=0``
    zero-tile references to the last output block: they keep the
    (o_row, o_col) sort order, hit a block every schedule has already
    initialized, and early-out in-kernel — a predicate, not a tile pass.
    """

    n_steps: int  # power-of-two padded grid length (shape class)
    n_tiles: int  # power-of-two padded per-site tile count (shape class)
    slots: tuple[int, ...]  # local site indices in this bucket
    sites: tuple[int, ...]  # global site ids, row-by-row (device-major)
    tiles: jnp.ndarray  # (axis_size * len(slots), n_tiles, *tile_shape)
    step: jnp.ndarray  # (rows, n_steps) int32 step words (frontier.pack_schedule)
    src: jnp.ndarray  # (rows, n_steps) int32
    dst: jnp.ndarray  # (rows, n_steps) int32


@dataclasses.dataclass
class ShardedLevelPlan:
    """Per-site fused level schedules, shape-bucketed.

    Site ``s`` holds an arbitrary edge partition; its tile lists are
    built from *its* edges only (:func:`stage_sharded_graph`, Stage A)
    and scheduled per automaton (Stage B).  Instead of padding every
    site to one global max grid, sites are grouped into a small set of
    power-of-two ``(n_steps, n_tiles)`` shape classes
    (:func:`bucket_staged_sites` picks the tile class per slot; the step
    class is the power-of-two roundup of the bucket members' longest
    schedule) — so padding waste stops growing with site count, and one
    ``vmap``-ped jitted program per bucket serves all of that bucket's
    sites under ``shard_map``.

    All bucket arrays are laid out for ``shard_map(in_specs=P(site_axes,
    ...))``: shard the leading (device-major) row dim, keep the rest
    replicated per device.  ``union_members`` is the fan-in union row
    layout shared by every site (callers extend the frontier once per
    level with :func:`extend_frontier`).
    """

    n_sites: int
    n_states: int
    n_nodes: int
    v_pad: int
    block_size: int
    q_pad: int
    axis_size: int
    union_members: tuple[tuple[int, ...], ...]
    buckets: tuple[PlanBucket, ...]
    n_real_steps: tuple[int, ...]  # per site: steps carrying a real tile
    useful_steps: int  # Σ per-site unpadded schedule lengths
    padded_steps: int  # Σ per-bucket rows × n_steps (executed grid slots)
    tile_dtype: str = "f32"  # dtype of the aliased bucket tile stacks

    @property
    def pad_waste_ratio(self) -> float:
        return self.padded_steps / max(self.useful_steps, 1)

    @property
    def bucket_shapes(self) -> tuple[tuple[int, int, int], ...]:
        """Per bucket: (n_steps class, n_tiles class, member rows)."""
        return tuple(
            (b.n_steps, b.n_tiles, len(b.sites)) for b in self.buckets
        )


def build_sharded_level_schedule(
    ca: CompiledAutomaton,
    staged: StagedShardedGraph,
    tile_buckets: ShardedTileBuckets | None = None,
    q_pad: int = QPAD,
    axis_size: int = 1,
    bucket_floor: int = BUCKET_FLOOR,
) -> ShardedLevelPlan:
    """Stage B: schedule one fused BFS level *per site* over the staged
    per-site tile slabs, bucketed into power-of-two shape classes.

    ``tile_buckets`` accepts the Stage-A shape buckets (e.g. from
    :class:`repro.core.plans.GraphPlanStore`, which caches them per
    (placement, axis_size)); without one they are built here.  A site
    holding zero edges (or none for some label) degenerates to a
    cover-only schedule in the smallest class.  The returned plan
    *aliases* the bucket tile stacks — the per-site packing and device
    transfer happened once in Stage A, so a new automaton signature on a
    hot graph costs only this host-side step indexing."""
    BUILD_COUNTERS["sharded_level_schedule"] += 1
    if tile_buckets is None:
        tile_buckets = bucket_staged_sites(staged, axis_size, bucket_floor)
    nb = staged.v_pad // staged.block_size
    frow_map, union_members = fanin_frontier_rows(ca)
    site_steps = [
        _schedule_steps(ca, offsets, nb, frow_map) for offsets in staged.site_offsets
    ]

    def pad_steps(col: np.ndarray, n_steps: int, fill: int) -> np.ndarray:
        return np.concatenate([col, np.full(n_steps - len(col), fill, np.int32)])

    buckets = []
    useful = sum(arr.shape[0] for arr, _, _, _ in site_steps)
    padded = 0
    for tb in tile_buckets.buckets:
        max_len = max(site_steps[s][0].shape[0] for s in tb.sites)
        # singleton buckets run at natural length — the pow2 roundup only
        # buys shape agreement between members, and padding steps are not
        # free (the interpreter pays most of a real step per slot)
        n_steps = (
            shape_class(max_len, tile_buckets.floor)
            if len(tb.sites) > 1
            else max_len
        )
        padded += n_steps * len(tb.sites)
        words = []
        for s in tb.sites:
            arr, fi, vl, _ = site_steps[s]
            words.append(pack_schedule(
                pad_steps(fi, n_steps, 0),
                pad_steps(vl, n_steps, 0),
                pad_steps(arr[:, 4], n_steps, 0),  # zero cover tile
                pad_steps(arr[:, 2], n_steps, 0),
                pad_steps(arr[:, 3], n_steps, 0),
                pad_steps(arr[:, 0], n_steps, ca.n_states - 1),
                pad_steps(arr[:, 1], n_steps, nb - 1),
            ))
        step, src, dst = (jnp.asarray(np.stack(w)) for w in zip(*words))
        buckets.append(
            PlanBucket(
                n_steps=n_steps,
                n_tiles=tb.n_tiles,
                slots=tb.slots,
                sites=tb.sites,
                tiles=tb.tiles,
                step=step,
                src=src,
                dst=dst,
            )
        )
    return ShardedLevelPlan(
        n_sites=staged.n_sites,
        n_states=ca.n_states,
        n_nodes=staged.n_nodes,
        v_pad=staged.v_pad,
        block_size=staged.block_size,
        q_pad=q_pad,
        axis_size=tile_buckets.axis_size,
        union_members=union_members,
        buckets=tuple(buckets),
        n_real_steps=tuple(n_real for _, _, _, n_real in site_steps),
        useful_steps=useful,
        padded_steps=padded,
        tile_dtype=staged.tile_dtype,
    )


def build_sharded_level_plan(
    ca: CompiledAutomaton,
    site_graphs: list[LabeledGraph] | StagedShardedGraph,
    block_size: int = 128,
    q_pad: int = QPAD,
    axis_size: int = 1,
    bucket_floor: int = BUCKET_FLOOR,
) -> ShardedLevelPlan:
    """One-shot wrapper: stage every site (Stage A), bucket the slabs
    into shape classes, then schedule (Stage B).  Pass a
    :class:`StagedShardedGraph` to skip straight to bucketing + Stage B —
    that is what :class:`repro.core.plans.GraphPlanStore` hands the
    sharded executor builder, making warm builds pack zero tiles."""
    staged = (
        site_graphs
        if isinstance(site_graphs, StagedShardedGraph)
        else stage_sharded_graph(site_graphs, block_size)
    )
    return build_sharded_level_schedule(
        ca, staged, q_pad=q_pad, axis_size=axis_size, bucket_floor=bucket_floor
    )


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "interpret", "union_members", "n_states"
    ),
)
def _fused_expand(
    frontier, tiles, step, src, dst,
    *, block_size, q_pad, interpret, union_members, n_states,
):
    fre = extend_frontier(frontier, union_members, n_states, q_pad)
    counts = fused_level_blocks(
        fre, tiles, step, src, dst,
        block_size, q_pad, interpret=interpret,
        n_out_rows=n_states * q_pad,
    )
    return jnp.minimum(counts, 1.0)


def expand_level_fused(
    plan: FusedLevelPlan,
    frontier: jnp.ndarray,  # (n_states * q_pad, v_pad) f32 0/1
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One BFS level over all grounded transitions — ONE pallas_call."""
    return _fused_expand(
        frontier, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad,
        interpret=resolve_interpret(interpret),
        union_members=plan.union_members, n_states=plan.n_states,
    )


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "max_levels", "interpret", "union_members", "n_states"
    ),
)
def _reach_fixpoint(
    frontier0, tiles, step, src, dst,
    *, block_size, q_pad, max_levels, interpret, union_members, n_states,
):
    """Device-resident BFS fixpoint: lax.while_loop over fused levels.

    The convergence reduction (``frontier.any()``) runs on device — the
    host is only reached once, when the final visited set is fetched.
    """

    def cond(state):
        _, frontier, lev = state
        return jnp.logical_and((frontier > 0).any(), lev < max_levels)

    def body(state):
        visited, frontier, lev = state
        fre = extend_frontier(frontier, union_members, n_states, q_pad)
        counts = fused_level_blocks(
            fre, tiles, step, src, dst,
            block_size, q_pad, interpret=interpret,
            n_out_rows=n_states * q_pad,
        )
        nxt = jnp.minimum(counts, 1.0)
        new = nxt * (1.0 - visited)  # exact on {0,1} floats
        return jnp.maximum(visited, new), new, lev + 1

    visited, _, _ = jax.lax.while_loop(
        cond, body, (frontier0, frontier0, jnp.int32(0))
    )
    return visited


def reach_fixpoint(
    plan: FusedLevelPlan,
    frontier0: jnp.ndarray,  # (n_states * q_pad, v_pad) f32 0/1
    max_levels: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Visited product states (same layout as ``frontier0``) at fixpoint."""
    return _reach_fixpoint(
        frontier0, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad,
        max_levels=max_levels, interpret=resolve_interpret(interpret),
        union_members=plan.union_members, n_states=plan.n_states,
    )


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "max_levels", "interpret", "union_members", "n_states"
    ),
)
def _reach_fixpoint_levels(
    frontier0, tiles, step, src, dst,
    *, block_size, q_pad, max_levels, interpret, union_members, n_states,
):
    """:func:`_reach_fixpoint` with the witness carry: alongside the
    visited plane, one f32 *discovery level* per (state row, node) —
    start pairs at level 1, a pair first reached by expansion ``i`` at
    level ``i + 1``, :data:`repro.core.witness.INF_LEVEL` when never
    reached.  Levels are implicit parent pointers (every discovered pair
    has a strictly-smaller-level product predecessor by construction),
    so the carry grows by exactly one plane — no per-edge pointers."""

    def cond(state):
        _, frontier, lev, _ = state
        return jnp.logical_and((frontier > 0).any(), lev < max_levels)

    def body(state):
        visited, frontier, lev, levels = state
        fre = extend_frontier(frontier, union_members, n_states, q_pad)
        counts = fused_level_blocks(
            fre, tiles, step, src, dst,
            block_size, q_pad, interpret=interpret,
            n_out_rows=n_states * q_pad,
        )
        nxt = jnp.minimum(counts, 1.0)
        new = nxt * (1.0 - visited)  # exact on {0,1} floats
        levels = jnp.where(new > 0, lev.astype(jnp.float32) + 2.0, levels)
        return jnp.maximum(visited, new), new, lev + 1, levels

    levels0 = jnp.where(frontier0 > 0, 1.0, INF_LEVEL)
    visited, _, _, levels = jax.lax.while_loop(
        cond, body, (frontier0, frontier0, jnp.int32(0), levels0)
    )
    return visited, levels


def _require_f32_tiles(plan: FusedLevelPlan, what: str) -> None:
    """The uint32 tile store carries one boolean bit per edge slot — a
    contract the witness-level and counting entry points refuse rather
    than silently extend: callers wanting those semirings restage at
    ``tile_dtype="f32"`` (the serve layer's witness fallback does exactly
    that — see ``repro.core.strategies``)."""
    if getattr(plan, "tile_dtype", "f32") != "f32":
        raise ValueError(
            f"{what} requires the f32 tile store; this plan aliases the "
            f"boolean-only tile_dtype={plan.tile_dtype!r} staging — restage "
            "with tile_dtype='f32' or use the boolean fixpoints"
        )


def reach_fixpoint_levels(
    plan: FusedLevelPlan,
    frontier0: jnp.ndarray,  # (n_states * q_pad, v_pad) f32 0/1
    max_levels: int = 64,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`reach_fixpoint` + BFS discovery levels (same layout, f32,
    ``INF_LEVEL`` = unreached) for host-side witness reconstruction.
    Refuses a ``tile_dtype="uint32"`` plan (boolean-only store)."""
    _require_f32_tiles(plan, "reach_fixpoint_levels")
    return _reach_fixpoint_levels(
        frontier0, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad,
        max_levels=max_levels, interpret=resolve_interpret(interpret),
        union_members=plan.union_members, n_states=plan.n_states,
    )


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "n_levels", "interpret", "union_members",
        "n_states", "accepting",
    ),
)
def _count_paths_bounded(
    frontier0, tiles, step, src, dst,
    *, block_size, q_pad, n_levels, interpret, union_members, n_states, accepting,
):
    acc_rows = jnp.asarray(accepting, jnp.int32)

    def accept_sum(counts3):
        return counts3[acc_rows].sum(axis=0)

    def body(_, state):
        counts, total = state
        fre = extend_frontier_sum(counts, union_members, n_states, q_pad)
        nxt = fused_level_blocks(
            fre, tiles, step, src, dst,
            block_size, q_pad, interpret=interpret,
            n_out_rows=n_states * q_pad,
        )
        nxt3 = nxt.reshape(n_states, q_pad, -1)
        return nxt, total + accept_sum(nxt3)

    f3 = frontier0.reshape(n_states, q_pad, -1)
    total0 = accept_sum(f3)
    _, total = jax.lax.fori_loop(0, n_levels, body, (frontier0, total0))
    return total


def count_paths_bounded(
    plan: FusedLevelPlan,
    frontier0: jnp.ndarray,  # (n_states * q_pad, v_pad) f32 start counts
    accepting: tuple[int, ...],
    n_levels: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Bounded-length counting-semiring sum over the SAME Stage-B level
    schedule the boolean fixpoint runs: drop the saturating ``min(·, 1)``
    clamp so the fused tile products accumulate exact run counts, sum
    fan-in unions instead of maxing them (:func:`extend_frontier_sum`),
    and total the accepting rows after every one of ``n_levels``
    expansions.  Returns (q_pad, v_pad) f32: per stacked query, the
    number of accepting *runs* of length ≤ ``n_levels`` from its starts
    to each node (run-based counting — an ambiguous automaton counts
    each of a walk's runs once; see :func:`repro.core.witness.count_paths`,
    the host oracle).

    Caveats: counts are exact f32 integers only below 2**24 (bound the
    length accordingly), and wildcard transitions ride the saturated
    any-label union store, so a wildcard hop counts parallel edges that
    carry different labels once, not per label — match the oracle on
    wildcard-free automata.  Refuses a ``tile_dtype="uint32"`` plan
    (the counting semiring is contracted to the f32 store)."""
    _require_f32_tiles(plan, "count_paths_bounded")
    return _count_paths_bounded(
        frontier0, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad, n_levels=n_levels,
        interpret=resolve_interpret(interpret), union_members=plan.union_members,
        n_states=plan.n_states, accepting=tuple(accepting),
    )


def stack_start_masks(
    plan: FusedLevelPlan, start_state: int, start_masks: np.ndarray
) -> np.ndarray:
    """Pack Q ≤ q_pad per-query start masks (Q, n_nodes) into the fused
    frontier layout (n_states * q_pad, v_pad): row s·q_pad + q is query
    q's frontier for automaton state s."""
    q = start_masks.shape[0]
    if q > plan.q_pad:
        raise ValueError(f"at most q_pad={plan.q_pad} stacked queries, got {q}")
    f0 = np.zeros((plan.n_states, plan.q_pad, plan.v_pad), np.float32)
    f0[start_state, :q, : start_masks.shape[1]] = start_masks
    return f0.reshape(plan.n_states * plan.q_pad, plan.v_pad)


def multi_query_reach(
    ca: CompiledAutomaton,
    bg: BlockedGraph,
    start_masks: np.ndarray,  # (Q, n_nodes) f32 0/1 — one row per query
    max_levels: int = 64,
    interpret: bool | None = None,
    plan: FusedLevelPlan | None = None,
) -> np.ndarray:
    """Fixpoint reachability for Q stacked queries; returns (Q, n_nodes)
    bool answer masks (nodes reached in an accepting state, per query).

    Queries ride the q_pad row dim in chunks of 8 — each chunk is ONE
    device-resident fixpoint (one jit call, zero host syncs between
    levels).  Pass a prebuilt ``plan`` to amortize schedule construction
    across calls.
    """
    start_masks = np.atleast_2d(np.asarray(start_masks, np.float32))
    if plan is None:
        plan = build_level_plan(ca, bg)
    n_q = start_masks.shape[0]
    out = np.zeros((n_q, bg.n_nodes), bool)
    for lo in range(0, n_q, plan.q_pad):
        chunk = start_masks[lo : lo + plan.q_pad]
        f0 = stack_start_masks(plan, ca.start, chunk)
        visited = np.asarray(
            reach_fixpoint(plan, jnp.asarray(f0), max_levels, interpret)
        ).reshape(plan.n_states, plan.q_pad, plan.v_pad)
        acc = np.zeros((plan.q_pad, plan.v_pad), np.float32)
        for qf in ca.accepting:
            acc = np.maximum(acc, visited[qf])
        out[lo : lo + chunk.shape[0]] = acc[: chunk.shape[0], : bg.n_nodes] > 0
    return out


def multi_source_reach(
    ca: CompiledAutomaton,
    bg: BlockedGraph,
    start_mask: np.ndarray,
    max_levels: int = 64,
    interpret: bool | None = None,
    plan: FusedLevelPlan | None = None,
) -> np.ndarray:
    """Single-query fixpoint reachability on the fused level kernel."""
    return multi_query_reach(
        ca, bg, np.asarray(start_mask, np.float32)[None, :],
        max_levels=max_levels, interpret=interpret, plan=plan,
    )[0]


# ---------------------------------------------------------------------------
# Bitpacked lane path: 256 query lanes per fixpoint (uint32 lane words)
# ---------------------------------------------------------------------------


def pack_lane_masks(masks: np.ndarray) -> np.ndarray:
    """Pack Q ≤ QPACK per-lane 0/1 masks (Q, n) into QPAD uint32 word
    rows (QPAD, n): lane q lands in word row ``q // 32``, bit ``q % 32``.
    Lanes past Q stay zero — the cross-lane leakage invariant starts
    here and the bitwise level/fixpoint ops preserve it."""
    masks = np.atleast_2d(np.asarray(masks))
    q, n = masks.shape
    if q > QPACK:
        raise ValueError(f"at most QPACK={QPACK} packed lanes, got {q}")
    words = np.zeros((QPAD, n), np.uint32)
    bits = masks != 0
    for lane in range(q):
        words[lane // 32] |= bits[lane].astype(np.uint32) << np.uint32(lane % 32)
    return words


def unpack_lane_words(words: np.ndarray, n_lanes: int) -> np.ndarray:
    """Inverse of :func:`pack_lane_masks`: the first ``n_lanes`` lanes of
    (QPAD, n) uint32 word rows as a (n_lanes, n) bool array."""
    words = np.asarray(words)
    out = np.zeros((n_lanes, words.shape[1]), bool)
    for lane in range(n_lanes):
        out[lane] = (words[lane // 32] >> np.uint32(lane % 32)) & 1 != 0
    return out


def stack_start_masks_packed(
    plan: FusedLevelPlan, start_state: int, start_masks: np.ndarray
) -> np.ndarray:
    """Pack Q ≤ QPACK per-query start masks (Q, n_nodes) into the packed
    frontier layout (n_states * q_pad, v_pad) uint32: word row
    s·q_pad + w carries lanes [32w, 32w+32) of automaton state s."""
    q = start_masks.shape[0]
    if q > QPACK:
        raise ValueError(f"at most QPACK={QPACK} stacked queries, got {q}")
    f0 = np.zeros((plan.n_states, plan.q_pad, plan.v_pad), np.uint32)
    f0[start_state, :, : start_masks.shape[1]] = pack_lane_masks(start_masks)
    return f0.reshape(plan.n_states * plan.q_pad, plan.v_pad)


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "interpret", "union_members", "n_states"
    ),
)
def _packed_expand(
    frontier, tiles, step, src, dst,
    *, block_size, q_pad, interpret, union_members, n_states,
):
    fre = extend_frontier_packed(frontier, union_members, n_states, q_pad)
    return packed_level_blocks(
        fre, tiles, step, src, dst,
        block_size, q_pad, interpret=interpret,
        n_out_rows=n_states * q_pad,
    )


def expand_level_packed(
    plan: FusedLevelPlan,
    frontier: jnp.ndarray,  # (n_states * q_pad, v_pad) uint32 lane words
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One packed BFS level over all grounded transitions — ONE
    pallas_call on the SAME Stage-B plan the f32 path uses (the staged
    f32 tiles are thresholded to bool in-kernel)."""
    return _packed_expand(
        frontier, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad,
        interpret=resolve_interpret(interpret),
        union_members=plan.union_members, n_states=plan.n_states,
    )


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "max_levels", "interpret", "union_members", "n_states"
    ),
)
def _reach_fixpoint_packed(
    frontier0, tiles, step, src, dst,
    *, block_size, q_pad, max_levels, interpret, union_members, n_states,
):
    """Device-resident packed BFS fixpoint: lax.while_loop over packed
    levels, converged via integer deltas (``frontier != 0``) — all 256
    lanes advance together and the loop exits when every lane's frontier
    word is zero."""

    def cond(state):
        _, frontier, lev = state
        return jnp.logical_and((frontier != 0).any(), lev < max_levels)

    def body(state):
        visited, frontier, lev = state
        fre = extend_frontier_packed(frontier, union_members, n_states, q_pad)
        nxt = packed_level_blocks(
            fre, tiles, step, src, dst,
            block_size, q_pad, interpret=interpret,
            n_out_rows=n_states * q_pad,
        )
        new = nxt & ~visited  # per-bit: newly discovered lanes only
        return visited | new, new, lev + 1

    visited, _, _ = jax.lax.while_loop(
        cond, body, (frontier0, frontier0, jnp.int32(0))
    )
    return visited


def reach_fixpoint_packed(
    plan: FusedLevelPlan,
    frontier0: jnp.ndarray,  # (n_states * q_pad, v_pad) uint32 lane words
    max_levels: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Visited lane words (same layout as ``frontier0``) at fixpoint."""
    return _reach_fixpoint_packed(
        frontier0, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad,
        max_levels=max_levels, interpret=resolve_interpret(interpret),
        union_members=plan.union_members, n_states=plan.n_states,
    )


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "q_pad", "max_levels", "interpret", "union_members", "n_states"
    ),
)
def _reach_fixpoint_packed_levels(
    frontier0, tiles, step, src, dst,
    *, block_size, q_pad, max_levels, interpret, union_members, n_states,
):
    """:func:`_reach_fixpoint_packed` with the witness carry.  The
    visited set stays bitpacked, but discovery levels are per *lane*, so
    the level plane is (n_states, q_pad·32, v_pad) f32 — 32× the packed
    word bytes (the price of witnesses at QPACK density; see the
    frontier README's witness-carry contract).  Newly-set bits of each
    expansion are transiently unpacked to stamp their lanes' levels."""
    bit_shifts = jnp.arange(32, dtype=jnp.uint32)

    def cond(state):
        _, frontier, lev, _ = state
        return jnp.logical_and((frontier != 0).any(), lev < max_levels)

    def body(state):
        visited, frontier, lev, levels = state
        fre = extend_frontier_packed(frontier, union_members, n_states, q_pad)
        nxt = packed_level_blocks(
            fre, tiles, step, src, dst,
            block_size, q_pad, interpret=interpret,
            n_out_rows=n_states * q_pad,
        )
        new = nxt & ~visited  # per-bit: newly discovered lanes only
        w3 = new.reshape(n_states, q_pad, -1)
        bits = (
            (w3[:, :, None, :] >> bit_shifts[None, None, :, None]) & jnp.uint32(1)
        ) != 0
        bits = bits.reshape(n_states, q_pad * 32, -1)
        levels = jnp.where(bits, lev.astype(jnp.float32) + 2.0, levels)
        return visited | new, new, lev + 1, levels

    v_pad = frontier0.shape[-1]
    f3 = frontier0.reshape(n_states, q_pad, v_pad)
    bits0 = (
        (f3[:, :, None, :] >> bit_shifts[None, None, :, None]) & jnp.uint32(1)
    ) != 0
    levels0 = jnp.where(
        bits0.reshape(n_states, q_pad * 32, v_pad), 1.0, INF_LEVEL
    )
    visited, _, _, levels = jax.lax.while_loop(
        cond, body, (frontier0, frontier0, jnp.int32(0), levels0)
    )
    return visited, levels


def reach_fixpoint_packed_levels(
    plan: FusedLevelPlan,
    frontier0: jnp.ndarray,  # (n_states * q_pad, v_pad) uint32 lane words
    max_levels: int = 64,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`reach_fixpoint_packed` + per-lane discovery levels:
    returns (visited lane words, levels) where levels is (n_states,
    QPACK, v_pad) f32 — lane q of word row ``q // 32``, bit ``q % 32``
    unpacks to level row q.  Refuses a ``tile_dtype="uint32"`` plan
    (witness levels are contracted to the f32 store)."""
    _require_f32_tiles(plan, "reach_fixpoint_packed_levels")
    return _reach_fixpoint_packed_levels(
        frontier0, plan.tiles, plan.step, plan.src, plan.dst,
        block_size=plan.block_size, q_pad=plan.q_pad,
        max_levels=max_levels, interpret=resolve_interpret(interpret),
        union_members=plan.union_members, n_states=plan.n_states,
    )


def multi_query_reach_packed(
    ca: CompiledAutomaton,
    bg: BlockedGraph,
    start_masks: np.ndarray,  # (Q, n_nodes) 0/1 — one row per query lane
    max_levels: int = 64,
    interpret: bool | None = None,
    plan: FusedLevelPlan | None = None,
) -> np.ndarray:
    """Fixpoint reachability for Q bitpacked queries; returns (Q,
    n_nodes) bool answer masks — bit-exact vs :func:`multi_query_reach`.

    Queries ride the bit axis in chunks of QPACK = 256: each chunk is
    ONE device-resident fixpoint over a frontier 32× denser than the
    f32 stacking (which needs 32 sequential QPAD-chunks for the same
    256 queries).  Pass a prebuilt ``plan`` to amortize schedule
    construction — the SAME plan object serves both dtypes."""
    start_masks = np.atleast_2d(np.asarray(start_masks))
    if plan is None:
        plan = build_level_plan(ca, bg)
    n_q = start_masks.shape[0]
    out = np.zeros((n_q, bg.n_nodes), bool)
    for lo in range(0, n_q, QPACK):
        chunk = start_masks[lo : lo + QPACK]
        f0 = stack_start_masks_packed(plan, ca.start, chunk)
        visited = np.asarray(
            reach_fixpoint_packed(plan, jnp.asarray(f0), max_levels, interpret)
        ).reshape(plan.n_states, plan.q_pad, plan.v_pad)
        acc = np.zeros((plan.q_pad, plan.v_pad), np.uint32)
        for qf in ca.accepting:
            acc |= visited[qf]
        out[lo : lo + chunk.shape[0]] = unpack_lane_words(acc, chunk.shape[0])[
            :, : bg.n_nodes
        ]
    return out


# ---------------------------------------------------------------------------
# Per-transition baseline (one dispatch per transition × label entry)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("block_size", "interpret"))
def _expand_one(frontier_row, tiles, rows, cols, *, block_size, interpret):
    """One (transition × label adjacency) block product, jitted.

    jit's cache is keyed on the argument *shapes* plus the static args, so
    each distinct (v_pad, nnz, block_size) combination traces the
    interpret-mode Pallas kernel exactly once per process — without this,
    every transition of every level of every graph re-traced it (the
    test_frontier_random_graph_sweep hang).  Only one frontier row is
    expanded per transition, so the kernel's row dim is the tile minimum
    (8) regardless of automaton size — keeping the cache key independent
    of n_states."""
    row_sel = (
        jnp.zeros((8, frontier_row.shape[0]), jnp.float32).at[0].set(frontier_row)
    )
    counts = frontier_step_blocks(
        row_sel, tiles, rows, cols, block_size, interpret=interpret
    )
    return jnp.minimum(counts[0], 1.0)


def expand_level(
    ca: CompiledAutomaton,
    bg: BlockedGraph,
    frontier: jnp.ndarray,  # (n_states, v_pad) f32 0/1 — rows = automaton states
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One BFS level over all grounded transitions; returns new 0/1 mask.

    Baseline path: one Pallas dispatch per transition × label entry plus
    a host-side merge — see :func:`expand_level_fused` for the fused
    single-dispatch form."""
    out = jnp.zeros((ca.n_states, bg.v_pad), jnp.float32)
    for t in ca.transitions:
        store = bg.fwd if t.direction == FWD else bg.inv
        if t.label_id >= 0:
            entries = [store.get(t.label_id)]
        else:  # wildcard
            entries = list(store.values())
        for entry in entries:
            if entry is None:
                continue
            tiles, rows, cols = entry
            counts = _expand_one(
                frontier[t.src], tiles, rows, cols,
                block_size=bg.block_size, interpret=resolve_interpret(interpret),
            )
            out = out.at[t.dst].max(counts)
    return (out > 0).astype(jnp.float32)


def multi_source_reach_baseline(
    ca: CompiledAutomaton,
    bg: BlockedGraph,
    start_mask: np.ndarray,
    max_levels: int = 64,
    interpret: bool | None = None,
) -> np.ndarray:
    """Fixpoint reachability with per-transition level dispatches and a
    host loop (one device→host sync per level) — the pre-fusion path,
    kept as the benchmark baseline."""
    frontier = np.zeros((ca.n_states, bg.v_pad), np.float32)
    frontier[ca.start, : len(start_mask)] = start_mask
    visited = frontier.copy()
    for _ in range(max_levels):
        nxt = np.asarray(expand_level(ca, bg, jnp.asarray(frontier), interpret))
        new = np.logical_and(nxt > 0, visited == 0)
        if not new.any():
            break
        visited = np.maximum(visited, new.astype(np.float32))
        frontier = new.astype(np.float32)
    acc = np.zeros(bg.v_pad, bool)
    for qf in ca.accepting:
        acc |= visited[qf] > 0
    return acc[: bg.n_nodes]
