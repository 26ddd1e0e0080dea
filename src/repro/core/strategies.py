"""Distributed RPQ processing strategies (paper §3) and message accounting.

Strategies:

* **S1 — top-down** (§3.3, §4.2.1): one broadcast of the query's distinct
  labels; every site unicasts its label-matching edges; the PAA then runs
  locally on the collected (deduplicated) subgraph.
* **S2 — bottom-up** (§3.3, §4.2.2): the PAA runs at the querying site;
  each BFS level's neighbor lookup is a broadcast search answered by the
  sites holding matching edges, with a local cache deduplicating repeated
  searches.
* **S3 — query shipping** (§3.1/§3.5.5): like S2 but subqueries are
  re-broadcast by a *different* site at every hop, so nothing can be
  cached.  Modeled by the instrumented PAA with the cache disabled.
* **S4 — query decomposition** (§3.2/§3.5.6): requires localized data; on
  non-localized data every edge is potentially "outgoing", so S4 sits at
  its degenerate bound — modeled analytically from placement statistics.

Execution vs accounting (DESIGN.md §2): the *executors* run S1/S2 with
real mesh collectives via ``jax.shard_map`` (sites = the ``data`` axis;
the query batch = the ``model`` axis); the *meters* count message symbols
with the paper's cost conventions (a symbol = one node id or label; an
edge = 3 symbols; broadcasting b symbols costs 2·N_c·b messages).

S2 has three interchangeable executor backends behind
:func:`make_s2_step_fn` — the ``shard_map`` gather/scatter reference,
the fused Pallas level kernel on global tiles (``frontier_kernel``),
and the site-sharded fused kernel (``frontier_kernel_sharded``: per-site
tile grids + per-level frontier merge, true per-site meters) — all
metering §4.2 with the same (symbol-set, node) broadcast-cache
semantics.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import spans
from repro.core import paa
from repro.core.automaton import FWD, CompiledAutomaton
from repro.core.regex import Node, has_wildcard, labels_of, query_size
from repro.core.witness import INF_LEVEL
from repro.graph.partition import OverlayNetwork, Placement
from repro.graph.structure import LabeledGraph

# ---------------------------------------------------------------------------
# Message accounting (the paper's cost metrics, §4.2)
# ---------------------------------------------------------------------------

EDGE_SYMBOLS = 3  # "an edge is expressed as 3 symbols" (§4.2.1)


@dataclasses.dataclass(frozen=True)
class StrategyCost:
    """Symbol counts for one query execution under one strategy.

    ``broadcast_symbols`` is the paper's Q_lbl (S1) / Q_bc (S2);
    ``unicast_symbols`` is D_s1 / D_s2 — *single-copy* data, the K
    replication multiplier is applied by the cost functions (Eqs. 1–2).

    ``site_unicast_symbols``, when non-empty, is the *measured* per-site
    response breakdown (raw symbols each site actually unicast, copies
    included — one entry per site).  Only site-aware executors (the
    ``frontier_kernel_sharded`` backend, the reference ``shard_map``
    executor does not expose it) fill it in; ``sum(site_unicast_symbols)``
    is then the true K-weighted response total that Eq. 2's ``k·D_s2``
    term estimates, and :func:`repro.core.cost_model.cost_of` prefers it
    over the estimate when present."""

    strategy: str
    broadcast_symbols: float
    unicast_symbols: float
    n_broadcasts: int = 0
    edges_retrieved: int = 0
    site_unicast_symbols: tuple[float, ...] = ()


def s1_costs(ast: Node, graph: LabeledGraph) -> StrategyCost:
    """§4.2.1: broadcast = #distinct labels; unicast = 3 × matching edges.

    A wildcard forces the full edge set (§3.6 — 'the mere presence of a
    wildcard is enough' to hit the worst case)."""
    lbls = labels_of(ast)
    lmap = graph.label_to_id
    if has_wildcard(ast):
        n_match = graph.n_edges
    else:
        ids = [lmap[l] for l in lbls if l in lmap]
        counts = graph.label_counts()
        n_match = int(sum(counts[i] for i in ids))
    return StrategyCost(
        strategy="S1",
        broadcast_symbols=float(len(lbls)),
        unicast_symbols=float(EDGE_SYMBOLS * n_match),
        n_broadcasts=1,
        edges_retrieved=n_match,
    )


def s2_costs(
    ca: CompiledAutomaton,
    index: paa.HostIndex,
    start_node: int,
    max_pops: int | None = None,
) -> StrategyCost:
    """§4.2.2: instrumented PAA (cache on).  Also usable as the §3.6
    'interruptible' capped execution via ``max_pops``."""
    tr = paa.run_instrumented(ca, index, start_node, max_pops=max_pops)
    return StrategyCost(
        strategy="S2",
        broadcast_symbols=float(tr.q_bc),
        unicast_symbols=float(tr.d_s2),
        n_broadcasts=tr.n_broadcasts,
        edges_retrieved=tr.edges_traversed,
    )


def s3_costs(ca: CompiledAutomaton, index: paa.HostIndex, start_node: int) -> StrategyCost:
    """§3.5.5: query shipping = S2's traversal with no cache (each hop's
    broadcast is issued by a different site, so nothing deduplicates)."""
    tr = _run_uncached(ca, index, start_node)
    return StrategyCost(
        strategy="S3",
        broadcast_symbols=float(tr.q_bc),
        unicast_symbols=float(tr.d_s2),
        n_broadcasts=tr.n_broadcasts,
        edges_retrieved=tr.edges_traversed,
    )


def s4_costs(ast: Node, graph: LabeledGraph, placement: Placement) -> StrategyCost:
    """§3.5.6 at the non-localized degenerate bound: sites must exchange
    their potentially-outgoing edges (all of them — K·|E| copies, 3 symbols
    each) before the one-round query; responses may carry the full traversed
    subgraph.  We charge the label-restricted subgraph as the response
    (the best case S4 could do with the paper's label selection)."""
    m = query_size(ast)
    K = placement.replication_factor
    bc = EDGE_SYMBOLS * K * graph.n_edges + m
    s1 = s1_costs(ast, graph)
    return StrategyCost(
        strategy="S4",
        broadcast_symbols=float(bc),
        unicast_symbols=float(s1.unicast_symbols),
        n_broadcasts=1 + placement.n_sites,
        edges_retrieved=s1.edges_retrieved,
    )


def _run_uncached(ca, index, start_node):
    """Instrumented PAA variant with the broadcast cache disabled (S3)."""
    graph = index.graph
    tr = paa.S2Trace()
    outs: dict[int, list] = {}
    for t in ca.transitions:
        outs.setdefault(t.src, []).append(t)
    state_symbols = {q: sorted({(t.label_id, t.direction) for t in ts}) for q, ts in outs.items()}
    visited = {(ca.start, int(start_node))}
    queue = [(ca.start, int(start_node))]
    accepting = set(ca.accepting)
    if ca.start in accepting:
        tr.answers.add(int(start_node))
    seen_edges: set[int] = set()
    while queue:
        q, v = queue.pop()
        tr.nodes_visited += 1
        symbols = state_symbols.get(q)
        if not symbols:
            continue
        tr.n_broadcasts += 1
        tr.q_bc += 1 + len(symbols)
        for (label_id, direction) in symbols:
            if label_id >= 0:
                eids = index.out_edges(v, label_id) if direction == FWD else index.in_edges(v, label_id)
            else:
                eids = index.all_out_edges(v) if direction == FWD else index.all_in_edges(v)
            tr.d_s2 += EDGE_SYMBOLS * len(eids)
            for e in eids:
                seen_edges.add(int(e) if direction == FWD else -int(e) - 1)
        for t in outs[q]:
            if t.label_id >= 0:
                eids = index.out_edges(v, t.label_id) if t.direction == FWD else index.in_edges(v, t.label_id)
            else:
                eids = index.all_out_edges(v) if t.direction == FWD else index.all_in_edges(v)
            nbrs = graph.dst[eids] if t.direction == FWD else graph.src[eids]
            for nb in nbrs:
                key = (t.dst, int(nb))
                if key not in visited:
                    visited.add(key)
                    queue.append(key)
                if t.dst in accepting:
                    tr.answers.add(int(nb))
    tr.edges_traversed = len(seen_edges)
    return tr


# ---------------------------------------------------------------------------
# S1 executor — one broadcast, one gather, local PAA
# ---------------------------------------------------------------------------


def s1_gather(
    mesh: Mesh,
    site_arrays: dict[str, np.ndarray],
    label_mask: np.ndarray,
    cap: int,
    site_axes: tuple[str, ...] = ("data",),
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Collect, from every site, its edges whose label is in ``label_mask``.

    Each site compacts matches to a static ``cap``-sized buffer (matched
    edges sorted first) and the buffers are all-gathered — the unicast
    response phase of S1 with static shapes.  ``cap`` is chosen by the
    planner from the D_s1 estimate (§5.2.2); the returned ``overflow``
    count is non-zero if any site had more matches than the buffer, in
    which case the caller re-runs with a larger cap.

    Returns (src, lbl, dst, valid_mask) of shape (n_sites, cap) plus the
    global overflow count.
    """
    n_sites = site_arrays["src"].shape[0]

    def local(src, lbl, dst, mask, lblmask):
        # src/lbl/dst/mask: (S_local, E) — one device may hold several sites
        def per_site(src, lbl, dst, mask):
            match = jnp.logical_and(mask, lblmask[lbl])
            # matched-first compaction: stable sort by ~match
            take = jnp.argsort(jnp.logical_not(match), stable=True)[:cap]
            overflow = jnp.maximum(match.sum() - cap, 0)
            return src[take], lbl[take], dst[take], match[take], overflow

        src, lbl, dst, match, overflow = jax.vmap(per_site)(src, lbl, dst, mask)
        return src, lbl, dst, match, overflow.sum()[None]

    spec_e = P(site_axes, None)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_e, spec_e, spec_e, spec_e, P()),
        out_specs=(spec_e, spec_e, spec_e, spec_e, P(site_axes)),
        check_vma=True,
    )
    src, lbl, dst, valid, overflow = fn(
        jnp.asarray(site_arrays["src"]),
        jnp.asarray(site_arrays["lbl"]),
        jnp.asarray(site_arrays["dst"]),
        jnp.asarray(site_arrays["mask"]),
        jnp.asarray(label_mask),
    )
    return (
        np.asarray(src),
        np.asarray(lbl),
        np.asarray(dst),
        np.asarray(valid),
        int(np.asarray(overflow).sum()),
    )


def query_label_mask(ast: Node, graph: LabeledGraph) -> np.ndarray:
    """(n_labels,) bool mask of the query's labels; all-True on wildcard
    (§3.6 — a wildcard defeats S1's label selection)."""
    mask = np.zeros(graph.n_labels, bool)
    if has_wildcard(ast):
        mask[:] = True
    else:
        lbl_ids = {graph.label_to_id[l] for l in labels_of(ast) if l in graph.label_to_id}
        mask[sorted(lbl_ids)] = True
    return mask


def s1_collect(
    mesh: Mesh,
    placement: Placement,
    label_mask: np.ndarray,
    cap: int | None = None,
    site_axes: tuple[str, ...] = ("data",),
    device_arrays: dict | None = None,
) -> LabeledGraph:
    """S1's retrieval phase: gather every site's ``label_mask``-matching
    edges and deduplicate the replicated copies at the querying site.

    Exposed separately from :func:`s1_execute` so the serve layer's
    batcher can retrieve the *union* subgraph of several coalesced S1
    queries with a single gather; ``device_arrays`` accepts the
    placement's already-staged padded site arrays (as in
    :func:`s2_execute`) so serving loops skip the per-call rebuild."""
    graph = placement.graph
    site_arrays = device_arrays if device_arrays is not None else placement.padded_device_arrays()
    if cap is None:
        cap = site_arrays["src"].shape[1]
    with spans.span("s1.collect") as sp:
        while True:
            with spans.span("s1.gather") as sg:
                src, lbl, dst, valid, overflow = s1_gather(
                    mesh, site_arrays, label_mask, cap, site_axes
                )
                sg.count("cap", cap)
                sg.count("bytes", src.nbytes + lbl.nbytes + dst.nbytes + valid.nbytes)
            if overflow == 0:
                break
            sp.count("retries")
            cap = min(2 * cap, site_arrays["src"].shape[1])  # planner underestimated: grow

        with spans.span("s1.dedup") as sd:
            v = valid.reshape(-1)
            sub = LabeledGraph(
                graph.n_nodes, src.reshape(-1)[v], lbl.reshape(-1)[v], dst.reshape(-1)[v],
                graph.labels,
            )
            sub = sub.dedup()  # replicated copies collapse at the querying site
            sd.count("edges", sub.n_edges)
        return sub


def s1_execute(
    mesh: Mesh,
    placement: Placement,
    ast: Node,
    ca: CompiledAutomaton,
    start_node: int,
    cap: int | None = None,
    site_axes: tuple[str, ...] = ("data",),
) -> tuple[set[int], StrategyCost]:
    """Full S1: broadcast labels → gather matching edges → dedup → local PAA."""
    graph = placement.graph
    label_mask = query_label_mask(ast, graph)
    sub = s1_collect(mesh, placement, label_mask, cap, site_axes)
    dg = paa.device_form(sub)
    acc = np.asarray(paa.answers_single_source(ca, dg, start_node))
    answers = set(np.nonzero(acc)[0].tolist())
    cost = s1_costs(ast, graph)
    return answers, cost


# ---------------------------------------------------------------------------
# S2 executor — frontier loop over sharded sites, batched queries
# ---------------------------------------------------------------------------


def _fuse_label_runs(ids: list[int]) -> list[tuple[int | None, int | None]]:
    """Fuse a sorted label-id list into contiguous (lo, hi) ranges; a
    negative id (wildcard) yields the (None, None) match-everything run."""
    runs: list[tuple[int | None, int | None]] = []
    if any(i < 0 for i in ids):
        runs.append((None, None))
    ids = sorted(i for i in ids if i >= 0)
    start = prev = None
    for i in ids:
        if start is None:
            start = prev = i
        elif i == prev + 1:
            prev = i
        else:
            runs.append((start, prev))
            start = prev = i
    if start is not None:
        runs.append((start, prev))
    return runs


def transition_runs(
    ca: CompiledAutomaton,
) -> tuple[tuple[int, int, int, int | None, int | None], ...]:
    """§Perf iteration 1 (label-range fusion): transitions that share
    (src_state, dst_state, direction) and carry *contiguous* label ids
    (the paper's C/A/I/E/P classes are contiguous in the vocabulary)
    fuse into ONE range predicate — q1 drops from 33 per-level edge
    scans to 5.

    The run list is also the executor's *structural signature*: two
    queries with equal runs (plus start/accepting states) compile to the
    same step function, which is what ``repro.serve``'s executor cache
    keys on.
    """
    from collections import defaultdict

    groups: dict[tuple[int, int, int], list[int]] = defaultdict(list)
    for t in ca.transitions:
        groups[(t.src, t.dst, t.direction)].append(t.label_id)
    runs: list[tuple[int, int, int, int | None, int | None]] = []
    for (s_st, d_st, direction), ids in sorted(groups.items()):
        for lo, hi in _fuse_label_runs(ids):
            runs.append((s_st, d_st, direction, lo, hi))
    return tuple(runs)


def symbol_set_groups(
    ca: CompiledAutomaton,
) -> tuple[tuple[tuple[tuple[int, int], ...], tuple[int, ...]], ...]:
    """Automaton states grouped by their out-symbol set, as
    ``((symset, states), ...)`` with ``symset`` the sorted distinct
    (label_id, direction) pairs.  States with no out-transitions issue no
    broadcast (§4.2.2) and are omitted.

    This is the §4.2.2 broadcast-cache key structure: the host meter
    caches by (node, symbol-set), so two *distinct* states sharing a
    symbol set must share one broadcast per node — the device meters key
    their dedup bitmaps by these groups to agree with the host
    (ROADMAP "Observed-cost fidelity")."""
    syms: dict[int, set] = {}
    for t in ca.transitions:
        syms.setdefault(t.src, set()).add((t.label_id, t.direction))
    groups: dict[tuple, list[int]] = {}
    for q, s in syms.items():
        groups.setdefault(tuple(sorted(s)), []).append(q)
    return tuple(
        sorted((symset, tuple(sorted(states))) for symset, states in groups.items())
    )


def site_sharding(mesh: Mesh, site_axes: tuple[str, ...] = ("data",)) -> NamedSharding:
    """Placement of per-site stacks (leading dim = sites, device-major):
    split over the mesh's site axes, so each device holds exactly the
    sites it runs — the site edge arrays and the sharded backend's tile
    buckets are staged this way once, instead of landing on the default
    device and moving on every call."""
    return NamedSharding(mesh, P(tuple(site_axes)))


def make_s2_step_fn(
    ca: CompiledAutomaton,
    n_nodes: int,
    mesh: Mesh,
    site_axes: tuple[str, ...] = ("data",),
    batch_axis: str | None = "model",
    max_levels: int | None = None,
    backend: str = "reference",
    graph: LabeledGraph | None = None,
    replication_factor: float = 1.0,
    block_size: int = 128,
    interpret: bool | None = None,
    placement: Placement | None = None,
    plan_store=None,
    stats_epoch: int = 0,
    bucket_floor: int | None = None,
    semantics: str = "pairs",
    tile_dtype: str = "f32",
    tile_store_budget_bytes: int | None = None,
):
    """Build the jitted batched S2 executor.

    Four backends share one call contract:

    * ``"reference"`` (default) — sites (edge shards) live on
      ``site_axes``; the query batch is sharded over ``batch_axis``.
      Each BFS level: every site matches *its* local edges against the
      (replicated) frontier and the per-site contributions are
      OR-combined with ``lax.pmax`` over the site axes — the collective
      realization of 'broadcast search + unicast responses'.

    * ``"frontier_kernel"`` — the fused Pallas level kernel: the whole
      BFS level over all transitions is ONE ``pallas_call`` on the
      block-sparse tiles of ``graph`` (required), with up to 8 queries
      stacked into the f32 row-tile minimum and a device-resident
      fixpoint (see :mod:`repro.kernels.frontier`).  ``interpret=None``
      auto-selects interpret mode off-TPU; ``replication_factor`` scales
      the returned unicast symbols to the reference backend's
      summed-per-site convention so :func:`s2_execute` can divide it
      back out.  Retrieval is modeled on the deduplicated *global*
      graph — the fastest path when one device can hold all tiles.

    * ``"frontier_kernel_packed"`` — the fused kernel with the frontier
      bitpacked into uint32 lane words: the same staged tiles and
      Stage-B schedule as ``"frontier_kernel"``, but each fixpoint
      chunk carries ``QPACK`` = 256 query lanes (8 word rows × 32 bits)
      instead of 8, at 1/32 the frontier HBM — bit-exact on the boolean
      semiring, with the §4.2 meters preserved per lane.

    * ``"frontier_kernel_sharded"`` — the fused kernel on *site-local*
      edge partitions (``placement`` required): each site's tile lists
      are built from its own edges and padded only up to the site's
      power-of-two *shape bucket* (``bucket_floor`` sets the smallest
      class), then run under ``shard_map`` over ``site_axes`` — one
      ``vmap``-ped fused call per bucket — with a double-buffered
      ``ppermute`` ring forwarding each iteration's discoveries while
      the next iteration's local expansion proceeds — the paper's
      distribution model (per-site local expansion + frontier exchange)
      on the fused Pallas path.  The §4.2 meters run per site on
      site-local degree vectors, so the returned costs carry the *true*
      per-site response breakdown instead of a replication-factor
      approximation.

    Returns ``fn(src, lbl, dst, mask, starts) -> (answers, q_bc, d_s2,
    n_bc)`` — the sharded backend appends a fifth output ``d_s2_sites``
    of shape (n_sites, B) — with shapes src/lbl/dst/mask: (n_sites,
    E_site) int32/bool; starts: (B,) int32; answers: (B, n_nodes) bool.
    The extra outputs are the *observed* §4.2 message accounting,
    computed in the loop itself: ``q_bc[i]`` is broadcast symbols,
    ``d_s2[i]`` is unicast response symbols summed over every site
    holding a matching edge (so replicated copies count, i.e. ≈ K·D_s2),
    and ``n_bc[i]`` is the number of distinct broadcast searches.  All
    meters deduplicate broadcasts by (symbol-set, node) — the §4.2.2
    cache key — so they agree with the host meter even when distinct
    states share a symbol set.

    Executor builds are **two-stage** (see :mod:`repro.core.plans`):
    pass ``plan_store`` (a :class:`~repro.core.plans.GraphPlanStore`)
    and the fused backends fetch their Stage-A artifacts — staged tile
    tensors, site-local graphs, degree vectors — from the store keyed by
    ``stats_epoch``, so only the cheap automaton-dependent Stage-B
    schedule is built here.  Without a store each build stages its own
    artifacts (the pre-refactor behavior, right for one-off callers).

    ``semantics="witness"`` grows every backend's fixpoint carry by one
    f32 *discovery level* plane (see :mod:`repro.core.witness`) and
    appends one output: ``levels`` of shape (B, n_states, n_nodes) f32,
    always LAST (after the sharded backend's ``d_s2_sites``) — level 1
    at the start pair, +1 per expansion, ``INF_LEVEL`` when unreached.
    Answers and meters are unchanged; the levels are the implicit parent
    pointers :func:`repro.core.witness.reconstruct_path` walks.

    ``tile_dtype="uint32"`` stages the bitpacked adjacency store (1/32
    the Stage-A bytes; kernels dispatch on the staged dtype, so the same
    plan shape serves both stores).  The bitpacked store is boolean-only:
    ``semantics="witness"`` silently falls back to f32 staging — the
    contracted store for discovery levels.  ``tile_store_budget_bytes``
    turns on the out-of-core tile store for the two *global* fused
    backends (requires ``plan_store``): Stage A assembles only the
    automaton's required (direction, label) slabs under a resident-byte
    budget, spilling cold slabs to disk (see
    :meth:`repro.core.plans.GraphPlanStore.staged_graph`).  The sharded
    backend honors the dtype but not the budget — its staging is
    per-placement slabs, out of scope for the global budget.
    """
    if semantics not in ("pairs", "witness"):
        raise ValueError(f"semantics must be 'pairs' or 'witness', got {semantics!r}")
    from repro.kernels.frontier.ref import TILE_DTYPES

    if tile_dtype not in TILE_DTYPES:
        raise ValueError(f"tile_dtype must be one of {TILE_DTYPES}, got {tile_dtype!r}")
    # the bitpacked store carries no counts and no room for witness-level
    # stamping contracts — witness semantics restages f32 (documented
    # fallback; the ops-level fixpoint wrappers *refuse* instead)
    eff_dtype = "f32" if semantics == "witness" else tile_dtype
    if backend == "frontier_kernel":
        return _make_frontier_step_fn(
            ca, n_nodes, max_levels, graph, replication_factor, block_size,
            interpret, plan_store, stats_epoch, semantics, eff_dtype,
            tile_store_budget_bytes,
        )
    if backend == "frontier_kernel_packed":
        return _make_frontier_packed_step_fn(
            ca, n_nodes, max_levels, graph, replication_factor, block_size,
            interpret, plan_store, stats_epoch, semantics, eff_dtype,
            tile_store_budget_bytes,
        )
    if backend == "frontier_kernel_sharded":
        return _make_frontier_sharded_step_fn(
            ca, n_nodes, mesh, site_axes, batch_axis, max_levels, placement,
            block_size, interpret, plan_store, stats_epoch, bucket_floor,
            semantics, eff_dtype,
        )
    if backend != "reference":
        raise ValueError(
            "backend must be 'reference', 'frontier_kernel', "
            "'frontier_kernel_packed', or 'frontier_kernel_sharded', "
            f"got {backend!r}"
        )
    witness = semantics == "witness"
    n_states = ca.n_states
    levels = max_levels if max_levels is not None else n_states * n_nodes

    # per-level edge masks are loop-invariant, so they are hoisted out of
    # the BFS while_loop (XLA cannot hoist across an opaque while body on
    # its own)
    runs = transition_runs(ca)
    sgroups = symbol_set_groups(ca)
    n_groups = max(len(sgroups), 1)

    def local(src, lbl, dst, mask, starts):
        # Any number of sites may live on one device; matching + scatter is
        # per-edge independent, so the local site block flattens into one
        # edge set (the OR over co-located sites is implicit).
        src, lbl, dst, mask = (a.reshape(-1) for a in (src, lbl, dst, mask))

        # loop-invariant per-run edge predicates (computed once per query)
        def range_sel(lo, hi):
            if lo is None:
                return mask
            return jnp.logical_and(mask, jnp.logical_and(lbl >= lo, lbl <= hi))

        sels = [range_sel(lo, hi) for (_, _, _, lo, hi) in runs]
        # per symbol-set group: fused label-range predicates by direction
        group_sels = []
        for symset, _ in sgroups:
            by_dir: dict[int, list[int]] = {}
            for lid, dirn in symset:
                by_dir.setdefault(dirn, []).append(lid)
            sels_g = []
            for dirn in sorted(by_dir):
                for lo, hi in _fuse_label_runs(by_dir[dirn]):
                    sels_g.append((dirn, range_sel(lo, hi)))
            group_sels.append(sels_g)

        def expand(frontier):
            nxt = jnp.zeros_like(frontier)
            for (s_st, d_st, direction, _, _), sel in zip(runs, sels):
                if direction == FWD:
                    bits = jnp.logical_and(frontier[s_st, src], sel)
                    contrib = jnp.zeros((n_nodes,), jnp.bool_).at[dst].max(bits)
                else:
                    bits = jnp.logical_and(frontier[s_st, dst], sel)
                    contrib = jnp.zeros((n_nodes,), jnp.bool_).at[src].max(bits)
                nxt = nxt.at[d_st].max(contrib)
            # unicast-response combine: OR over every site holding a copy
            for ax in site_axes:
                nxt = jax.lax.pmax(nxt, ax)
            return nxt

        def one_query(s0):
            visited0 = jnp.zeros((n_states, n_nodes), jnp.bool_).at[ca.start, s0].set(True)
            done0 = jnp.zeros((n_groups, n_nodes), jnp.bool_)

            def cond(state):
                frontier, lev = state[1], state[2]
                return jnp.logical_and(frontier.any(), lev < levels)

            def body(state):
                visited, frontier, lev, done, q_bc, d_s2, n_bc = state[:7]
                # observed accounting: the frontier is exactly the set of
                # newly visited product states; a broadcast is charged the
                # first time a (symbol-set, node) pair appears across ALL
                # states of the group — the §4.2.2 cache, matching the
                # host meter when distinct states share a symbol set
                new_done = []
                for gi, (symset, states_g) in enumerate(sgroups):
                    now_g = frontier[states_g[0]]
                    for s_st in states_g[1:]:
                        now_g = jnp.logical_or(now_g, frontier[s_st])
                    new_g = jnp.logical_and(now_g, jnp.logical_not(done[gi]))
                    n_new = new_g.sum()
                    q_bc = q_bc + (1 + len(symset)) * n_new.astype(jnp.float32)
                    n_bc = n_bc + n_new
                    for dirn, asel in group_sels[gi]:
                        end = src if dirn == FWD else dst
                        hits = jnp.logical_and(new_g[end], asel)
                        d_s2 = d_s2 + EDGE_SYMBOLS * hits.sum().astype(jnp.float32)
                    new_done.append(jnp.logical_or(done[gi], now_g))
                if new_done:
                    done = jnp.stack(new_done)
                new = jnp.logical_and(expand(frontier), jnp.logical_not(visited))
                out = (
                    jnp.logical_or(visited, new), new, lev + 1, done,
                    q_bc, d_s2, n_bc,
                )
                if witness:
                    # expand() pmax-merges over site_axes, so `new` (and
                    # thus the stamped levels) is identical on every site
                    levmap = jnp.where(
                        new, lev.astype(jnp.float32) + 2.0, state[7]
                    )
                    out = out + (levmap,)
                return out

            state0 = (
                visited0, visited0, jnp.int32(0), done0,
                jnp.float32(0), jnp.float32(0), jnp.int32(0),
            )
            if witness:
                state0 = state0 + (jnp.where(visited0, 1.0, INF_LEVEL),)
            final = jax.lax.while_loop(cond, body, state0)
            visited, q_bc, d_s2, n_bc = final[0], final[4], final[5], final[6]
            acc = jnp.zeros((n_nodes,), jnp.bool_)
            for qf in ca.accepting:
                acc = jnp.logical_or(acc, visited[qf])
            # total unicast symbols: every site holding a matching edge
            # answers the broadcast, so sum the per-site counts
            for ax in site_axes:
                d_s2 = jax.lax.psum(d_s2, ax)
            if witness:
                return acc, q_bc, d_s2, n_bc, final[7]
            return acc, q_bc, d_s2, n_bc

        return jax.vmap(one_query)(starts)

    spec_e = P(site_axes, None)
    spec_b = P(batch_axis) if batch_axis else P()
    out_b = P(batch_axis) if batch_axis else P()
    out_specs = (
        P(batch_axis, None) if batch_axis else P(None, None),
        out_b,
        out_b,
        out_b,
    )
    if witness:
        out_specs = out_specs + (
            P(batch_axis, None, None) if batch_axis else P(None, None, None),
        )
    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, spec_e, spec_b),
            out_specs=out_specs,
            check_vma=False,
        )
    )


def _fetch_staged_graph(
    ca: CompiledAutomaton,
    graph: LabeledGraph,
    block_size: int,
    plan_store,
    stats_epoch: int,
    tile_dtype: str,
    budget_bytes: int | None,
):
    """Stage-A fetch shared by the two global fused builders: from the
    plan store when one is passed (budgeted path assembles only the
    automaton's required (direction, label) slabs), staged locally
    otherwise.  The budget requires a store — the out-of-core slab cache
    lives in the :class:`~repro.core.plans.GraphPlanStore`."""
    from repro.kernels.frontier import ops as fops

    if plan_store is not None:
        if budget_bytes is not None:
            return plan_store.staged_graph(
                graph, block_size, epoch=stats_epoch, tile_dtype=tile_dtype,
                budget_bytes=budget_bytes, keys=fops.required_offset_keys(ca),
            )
        return plan_store.staged_graph(
            graph, block_size, epoch=stats_epoch, tile_dtype=tile_dtype
        )
    if budget_bytes is not None:
        raise ValueError(
            "tile_store_budget_bytes requires plan_store= (the out-of-core "
            "slab cache lives in the GraphPlanStore)"
        )
    return fops.stage_graph(graph, block_size, tile_dtype=tile_dtype)


def _make_frontier_step_fn(
    ca: CompiledAutomaton,
    n_nodes: int,
    max_levels: int | None,
    graph: LabeledGraph | None,
    replication_factor: float,
    block_size: int,
    interpret: bool | None,
    plan_store=None,
    stats_epoch: int = 0,
    semantics: str = "pairs",
    tile_dtype: str = "f32",
    tile_store_budget_bytes: int | None = None,
):
    """The fused-Pallas S2 executor (``backend="frontier_kernel"``).

    Stage A (the global graph's staged block-sparse tile tensor and the
    per-label degree vectors) comes from ``plan_store`` when one is
    passed — shared across every automaton signature — and is staged
    locally otherwise; only the cheap automaton-dependent Stage-B level
    schedule is built per executor.  Each call stacks the start
    batch into chunks of ``QPAD`` (=8) queries riding the f32 row-tile
    minimum, and runs one device-resident fixpoint per chunk — one
    ``pallas_call`` per BFS level regardless of |transitions| × |labels|,
    zero host syncs between levels.  The site arrays of the shared step
    contract are accepted and ignored: retrieval is modeled on the
    deduplicated global graph, with ``replication_factor`` scaling d_s2
    back to the per-site-summed convention — use
    :func:`_make_frontier_sharded_step_fn` when retrieval must honor the
    actual site partition.

    The §4.2 observed accounting runs inside the same fixpoint on
    precomputed per-(symbol-set group) degree vectors, with a
    (group, node) dedup bitmap in the loop carry — the same symbol-set
    cache semantics as the host meter.
    """
    from repro.kernels.frontier import frontier as fkernel
    from repro.kernels.frontier import ops as fops

    if graph is None:
        raise ValueError(
            "backend='frontier_kernel' requires graph= (the placement's global graph)"
        )
    if graph.n_nodes != n_nodes:
        raise ValueError(f"graph has {graph.n_nodes} nodes, executor built for {n_nodes}")
    interpret = fkernel.resolve_interpret(interpret)
    staged = _fetch_staged_graph(
        ca, graph, block_size, plan_store, stats_epoch, tile_dtype,
        tile_store_budget_bytes,
    )
    plan = fops.build_level_schedule(ca, staged)
    n_states, q_pad, v_pad = ca.n_states, plan.q_pad, plan.v_pad
    witness = semantics == "witness"
    levels = max_levels if max_levels is not None else n_states * n_nodes

    sgroups = symbol_set_groups(ca)
    n_groups = max(len(sgroups), 1)
    # matching-edge counts per node for each group's symbol set: the
    # unicast response size of one broadcast at that node (§4.2.2)
    label_deg = (
        plan_store.label_degrees(graph, [graph], graph.n_labels, v_pad, epoch=stats_epoch)
        if plan_store is not None
        else None
    )
    deg, payloads = _site_symbol_degrees(sgroups, [graph], v_pad, label_deg)
    operands = (*_schedule_operands(plan), jnp.asarray(deg[0]), jnp.asarray(payloads))
    state_rows = [jnp.asarray(states, jnp.int32) for _, states in sgroups]

    def fixpoint(f0, operands):  # (n_states, q_pad, v_pad) f32 0/1
        sched, deg_c, pay_c = operands[:8], operands[8], operands[9]
        flat0 = f0.reshape(n_states * q_pad, v_pad)
        zero_q = jnp.zeros((q_pad,), jnp.float32)

        def cond(state):
            _, frontier, lev = state[:3]
            return jnp.logical_and((frontier > 0).any(), lev < levels)

        def body(state):
            visited, frontier, lev, done, q_bc, d_s2, n_bc = state[:7]
            fr3 = frontier.reshape(n_states, q_pad, v_pad)
            new_done = []
            for gi, rows in enumerate(state_rows):
                now_g = fr3[rows].max(axis=0)  # (q_pad, v_pad)
                new_g = now_g * (1.0 - done[gi])
                cnt = new_g.sum(axis=1)
                q_bc = q_bc + pay_c[gi] * cnt
                n_bc = n_bc + cnt
                d_s2 = d_s2 + EDGE_SYMBOLS * (new_g * deg_c[gi]).sum(axis=1)
                new_done.append(jnp.maximum(done[gi], now_g))
            done = jnp.stack(new_done) if new_done else done
            fre = fops.extend_frontier(
                frontier, plan.union_members, n_states, q_pad
            )
            counts = fkernel.fused_level_blocks(
                fre, *sched, plan.block_size, q_pad, interpret=interpret,
                n_out_rows=n_states * q_pad,
            )
            nxt = jnp.minimum(counts, 1.0)
            new = nxt * (1.0 - visited)
            out = (
                jnp.maximum(visited, new), new, lev + 1, done, q_bc, d_s2, n_bc
            )
            if witness:
                levmap = jnp.where(
                    new > 0, lev.astype(jnp.float32) + 2.0, state[7]
                )
                out = out + (levmap,)
            return out

        state0 = (
            flat0, flat0, jnp.int32(0),
            jnp.zeros((n_groups, q_pad, v_pad), jnp.float32), zero_q, zero_q, zero_q,
        )
        if witness:
            state0 = state0 + (jnp.where(flat0 > 0, 1.0, INF_LEVEL),)
        final = jax.lax.while_loop(cond, body, state0)
        visited, q_bc, d_s2, n_bc = final[0], final[4], final[5], final[6]
        vis3 = visited.reshape(n_states, q_pad, v_pad)
        acc = jnp.zeros((q_pad, v_pad), jnp.float32)
        for qf in ca.accepting:
            acc = jnp.maximum(acc, vis3[qf])
        out = (acc[:, :n_nodes] > 0, q_bc, d_s2 * replication_factor, n_bc)
        if witness:
            levmap = final[7].reshape(n_states, q_pad, v_pad)
            out = out + (levmap.transpose(1, 0, 2)[:, :, :n_nodes],)
        return out

    def run(operands, starts):
        b = starts.shape[0]
        n_chunks = -(-b // q_pad)
        pad = n_chunks * q_pad - b
        if pad:
            starts = jnp.concatenate([starts, jnp.zeros((pad,), starts.dtype)])
        chunks = starts.reshape(n_chunks, q_pad)

        def one_chunk(schunk):
            f0 = (
                jnp.zeros((n_states, q_pad, v_pad), jnp.float32)
                .at[ca.start, jnp.arange(q_pad), schunk]
                .set(1.0)
            )
            return fixpoint(f0, operands)

        out = jax.lax.map(one_chunk, chunks)
        acc, q_bc, d_s2, n_bc = out[:4]
        res = (
            acc.reshape(n_chunks * q_pad, n_nodes)[:b],
            q_bc.reshape(-1)[:b],
            d_s2.reshape(-1)[:b],
            n_bc.reshape(-1)[:b].astype(jnp.int32),
        )
        if witness:
            res = res + (
                out[4].reshape(n_chunks * q_pad, n_states, n_nodes)[:b],
            )
        return res

    # retrieval is modeled on the staged global tiles
    return _bind_operands(run, operands, interpret)


def _make_frontier_packed_step_fn(
    ca: CompiledAutomaton,
    n_nodes: int,
    max_levels: int | None,
    graph: LabeledGraph | None,
    replication_factor: float,
    block_size: int,
    interpret: bool | None,
    plan_store=None,
    stats_epoch: int = 0,
    semantics: str = "pairs",
    tile_dtype: str = "f32",
    tile_store_budget_bytes: int | None = None,
):
    """The bitpacked fused-Pallas S2 executor
    (``backend="frontier_kernel_packed"``).

    Same Stage A and Stage B as :func:`_make_frontier_step_fn` — the
    staged f32 tile tensor is shared (the packed kernel thresholds it to
    bool in-kernel) and the level schedule is the identical plan object
    — but the frontier carry is uint32 lane *words*: chunk lane ``q``
    lives in word row ``q // 32``, bit ``q % 32``, so one
    device-resident fixpoint answers ``QPACK`` = 256 queries at 1/32
    the frontier HBM of f32 stacking.  Convergence is integer deltas
    (``frontier != 0``) in the same ``lax.while_loop`` shape.

    The §4.2 observed accounting is preserved *per lane*: the
    (group, node) dedup bitmap stays packed in the carry, and each
    level's newly-broadcast lanes are transiently bit-unpacked to f32
    only for the per-lane count/degree dot products — q_bc/d_s2/n_bc
    come back per query, identical to the f32 backend's meters.

    Under ``semantics="witness"`` the visited/frontier words stay
    packed, but discovery levels are per *lane*: the level plane is
    (n_states, QPACK, v_pad) f32 per chunk — 32× the packed word bytes
    (the price of witnesses at QPACK density; the 1/32 frontier-HBM win
    applies to the boolean carry only).
    """
    from repro.kernels.frontier import frontier as fkernel
    from repro.kernels.frontier import ops as fops

    if graph is None:
        raise ValueError(
            "backend='frontier_kernel_packed' requires graph= "
            "(the placement's global graph)"
        )
    if graph.n_nodes != n_nodes:
        raise ValueError(f"graph has {graph.n_nodes} nodes, executor built for {n_nodes}")
    interpret = fkernel.resolve_interpret(interpret)
    staged = _fetch_staged_graph(
        ca, graph, block_size, plan_store, stats_epoch, tile_dtype,
        tile_store_budget_bytes,
    )
    plan = fops.build_level_schedule(ca, staged)
    n_states, q_pad, v_pad = ca.n_states, plan.q_pad, plan.v_pad
    q_pack = fops.QPACK
    witness = semantics == "witness"
    levels = max_levels if max_levels is not None else n_states * n_nodes

    sgroups = symbol_set_groups(ca)
    n_groups = max(len(sgroups), 1)
    label_deg = (
        plan_store.label_degrees(graph, [graph], graph.n_labels, v_pad, epoch=stats_epoch)
        if plan_store is not None
        else None
    )
    deg, payloads = _site_symbol_degrees(sgroups, [graph], v_pad, label_deg)
    operands = (*_schedule_operands(plan), jnp.asarray(deg[0]), jnp.asarray(payloads))
    state_rows = [jnp.asarray(states, jnp.int32) for _, states in sgroups]
    bit_shifts = jnp.arange(32, dtype=jnp.uint32)

    def lane_bits(words):  # (q_pad, v_pad) u32 -> (q_pack, v_pad) f32 0/1
        bits = (words[:, None, :] >> bit_shifts[None, :, None]) & jnp.uint32(1)
        return bits.astype(jnp.float32).reshape(q_pack, v_pad)

    def state_lane_bits(flat):  # (n_states*q_pad, v_pad) u32 -> bool lanes
        w3 = flat.reshape(n_states, q_pad, v_pad)
        bits = (
            (w3[:, :, None, :] >> bit_shifts[None, None, :, None]) & jnp.uint32(1)
        ) != 0
        return bits.reshape(n_states, q_pack, v_pad)

    def fixpoint(f0, operands):  # (n_states, q_pad, v_pad) uint32 lane words
        sched, deg_c, pay_c = operands[:8], operands[8], operands[9]
        flat0 = f0.reshape(n_states * q_pad, v_pad)
        zero_q = jnp.zeros((q_pack,), jnp.float32)

        def cond(state):
            _, frontier, lev = state[:3]
            return jnp.logical_and((frontier != 0).any(), lev < levels)

        def body(state):
            visited, frontier, lev, done, q_bc, d_s2, n_bc = state[:7]
            fr3 = frontier.reshape(n_states, q_pad, v_pad)
            new_done = []
            with jax.named_scope("rpq/meters"):
                for gi, rows in enumerate(state_rows):
                    now_g = jax.lax.reduce(
                        fr3[rows], jnp.uint32(0), jax.lax.bitwise_or, (0,)
                    )  # (q_pad, v_pad) lane words
                    new_g = now_g & ~done[gi]
                    bits = lane_bits(new_g)  # per-lane 0/1, meter dots only
                    cnt = bits.sum(axis=1)
                    q_bc = q_bc + pay_c[gi] * cnt
                    n_bc = n_bc + cnt
                    d_s2 = d_s2 + EDGE_SYMBOLS * (bits * deg_c[gi][None, :]).sum(axis=1)
                    new_done.append(done[gi] | now_g)
                done = jnp.stack(new_done) if new_done else done
            fre = fops.extend_frontier_packed(
                frontier, plan.union_members, n_states, q_pad
            )
            nxt = fkernel.packed_level_blocks(
                fre, *sched, plan.block_size, q_pad, interpret=interpret,
                n_out_rows=n_states * q_pad,
            )
            new = nxt & ~visited
            out = (visited | new, new, lev + 1, done, q_bc, d_s2, n_bc)
            if witness:
                levmap = jnp.where(
                    state_lane_bits(new),
                    lev.astype(jnp.float32) + 2.0,
                    state[7],
                )
                out = out + (levmap,)
            return out

        state0 = (
            flat0, flat0, jnp.int32(0),
            jnp.zeros((n_groups, q_pad, v_pad), jnp.uint32), zero_q, zero_q, zero_q,
        )
        if witness:
            state0 = state0 + (
                jnp.where(state_lane_bits(flat0), 1.0, INF_LEVEL),
            )
        with jax.named_scope("rpq/fixpoint"):
            final = jax.lax.while_loop(cond, body, state0)
        visited, lev, q_bc, d_s2, n_bc = final[0], final[2], final[4], final[5], final[6]
        with jax.named_scope("rpq/answers"):
            vis3 = visited.reshape(n_states, q_pad, v_pad)
            acc = jnp.zeros((q_pad, v_pad), jnp.uint32)
            for qf in ca.accepting:
                acc = acc | vis3[qf]
            answers = lane_bits(acc)[:, :n_nodes] > 0
        out = (answers, q_bc, d_s2 * replication_factor, n_bc)
        if witness:
            # (n_states, q_pack, v_pad) -> (q_pack, n_states, n_nodes)
            out = out + (final[7].transpose(1, 0, 2)[:, :, :n_nodes],)
        return out + (lev,)

    lane_ids = jnp.arange(q_pack, dtype=jnp.int32)

    def run(operands, starts):
        b = starts.shape[0]
        n_chunks = -(-b // q_pack)
        pad = n_chunks * q_pack - b
        if pad:
            starts = jnp.concatenate([starts, jnp.zeros((pad,), starts.dtype)])
        chunks = starts.reshape(n_chunks, q_pack)

        def one_chunk(schunk):
            # lanes carry distinct bits within a word row, so scatter-add
            # IS scatter-OR even when two lanes start at the same node
            f0 = (
                jnp.zeros((n_states, q_pad, v_pad), jnp.uint32)
                .at[ca.start, lane_ids // 32, schunk]
                .add(jnp.uint32(1) << (lane_ids % 32).astype(jnp.uint32))
            )
            return fixpoint(f0, operands)

        out = jax.lax.map(one_chunk, chunks)
        acc, q_bc, d_s2, n_bc = out[:4]
        res = (
            acc.reshape(n_chunks * q_pack, n_nodes)[:b],
            q_bc.reshape(-1)[:b],
            d_s2.reshape(-1)[:b],
            n_bc.reshape(-1)[:b].astype(jnp.int32),
        )
        if witness:
            res = res + (
                out[4].reshape(n_chunks * q_pack, n_states, n_nodes)[:b],
            )
        # the level kernel's calls, all chunks: one scalar, read back
        # only while spans record (s2_execute)
        return res + (out[-1].sum(),)

    # retrieval is modeled on the staged global tiles
    return _bind_operands(run, operands, interpret, kernel_bytes=plan.kernel_bytes)


def _schedule_operands(plan) -> tuple:
    """A fused plan's tile store and Stage-B step arrays, in the order
    :func:`repro.kernels.frontier.frontier.fused_level_blocks` takes
    them (the same order for ``packed_level_blocks``)."""
    return (
        plan.tiles, plan.firsts, plan.valids, plan.tile_ids,
        plan.f_rows, plan.f_cols, plan.o_rows, plan.o_cols,
    )


def _bind_operands(run, operands, interpret: bool, kernel_bytes: int | None = None):
    """Wrap ``run(operands, starts)`` in the shared step contract
    ``fn(src, lbl, dst, mask, starts)`` of the fused executors; they
    ignore the site edge arrays and read only their staged operands.

    The staged device arrays (tile store, Stage-B schedule, meter
    vectors) are passed to the jitted program as arguments: an array
    the program closed over would be embedded in it as a constant, so
    every executor would compile, cache and hold its own copy of the
    tile store.  ``fn.interpret`` records the resolved Pallas mode, and
    ``fn.clear_cache`` drops the compiled programs on eviction.  Where
    ``kernel_bytes`` (the level kernel's HBM bytes per call) is given,
    ``run``'s last output is the number of level-kernel calls it made,
    and ``fn.kernel_bytes`` says so to :func:`s2_execute`."""
    jitted = jax.jit(run)

    def fn(src, lbl, dst, mask, starts):
        del src, lbl, dst, mask
        return jitted(operands, starts)

    fn.clear_cache = jitted.clear_cache
    fn.interpret = interpret
    fn.kernel_bytes = kernel_bytes
    return fn


def _site_symbol_degrees(
    sgroups, site_graphs, v_pad: int, label_deg: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Per-site, per-symbol-set-group matching-edge counts by node.

    ``deg[s, g, v]`` is the number of edges site ``s`` holds that match
    group ``g``'s symbol set and are incident (in the search direction)
    to node ``v`` — the unicast response size site ``s`` contributes to
    one broadcast at ``v`` (§4.2.2).  ``payloads[g]`` is the broadcast
    payload 1 + |symset|.

    ``label_deg`` accepts the Stage-A per-(site, label, direction)
    vectors from :func:`repro.core.plans.label_degree_vectors`: the
    automaton-dependent group vectors then reduce to row sums (a
    wildcard sums every label — each edge has exactly one label), so
    warm executor builds skip the per-edge ``np.add.at`` scans.
    """
    n_groups = max(len(sgroups), 1)
    deg = np.zeros((len(site_graphs), n_groups, v_pad), np.float32)
    payloads = np.zeros(n_groups, np.float32)
    for gi, (symset, _) in enumerate(sgroups):
        payloads[gi] = 1 + len(symset)
        if label_deg is not None:
            for lid, dirn in symset:
                d = 0 if dirn == FWD else 1
                if lid < 0:
                    deg[:, gi] += label_deg[:, :, d].sum(axis=1)
                else:
                    deg[:, gi] += label_deg[:, lid, d]
            continue
        for s, g_s in enumerate(site_graphs):
            for lid, dirn in symset:
                sel = slice(None) if lid < 0 else g_s.lbl == lid
                ends = (g_s.src if dirn == FWD else g_s.dst)[sel]
                np.add.at(deg[s, gi], ends, 1.0)
    return deg, payloads


def _make_frontier_sharded_step_fn(
    ca: CompiledAutomaton,
    n_nodes: int,
    mesh: Mesh,
    site_axes: tuple[str, ...],
    batch_axis: str | None,
    max_levels: int | None,
    placement: Placement | None,
    block_size: int,
    interpret: bool | None,
    plan_store=None,
    stats_epoch: int = 0,
    bucket_floor: int | None = None,
    semantics: str = "pairs",
    tile_dtype: str = "f32",
):
    """The site-sharded fused-Pallas S2 executor
    (``backend="frontier_kernel_sharded"``).

    Stage A — the per-site staged tile slabs, their device-granular
    merge, its shape buckets, site-local graph views, and per-label
    degree vectors (n_sites packings per build without sharing!) —
    comes from ``plan_store`` when one is passed; only the
    automaton-dependent Stage-B schedule is built per executor.

    Honors the paper's distribution model on the fused kernel path: each
    device's block-sparse tiles come from its own sites' edge partitions
    (replication included), merged into one deduplicated union grid per
    device (:func:`repro.kernels.frontier.ops.merge_staged_sites` —
    boolean-semiring levels are identical on the union, and per-site
    identity lives in the §4.2 meters and the cross-device exchange,
    not in the expansion tiles) and padded only to the device's
    power-of-two *shape bucket*
    (see :func:`repro.kernels.frontier.ops.bucket_staged_sites`) —
    never to the worst device's grid, and not at all when the bucket has
    a single member — so padding waste stays bounded as site counts
    grow, and all of a bucket's member rows run as ONE ``vmap``-ped
    fused call.  One fixpoint iteration is then, under ``shard_map`` over
    ``site_axes``:

        local expansion   — per shape bucket, one (vmapped)
                            ``fused_level_blocks`` call over this
                            device's member sites (padding steps
                            early-out in-kernel via the ``valids``
                            prefetch flag),
        frontier exchange — a double-buffered ring: each iteration
                            ``lax.ppermute`` forwards the *previous*
                            iteration's discoveries one hop along each
                            site axis while the local expansion of this
                            iteration proceeds — the permute is
                            data-independent of the local compute, so
                            the two overlap instead of serializing on a
                            per-level ``pmax``,
        convergence       — an ``active`` flag ``psum``-reduced at the
                            *end* of each body (the while cond itself
                            stays collective-free); every discovery
                            travels the ring at most once, suppressed at
                            the first device that already visited it, so
                            the per-device visited sets converge to the
                            same global fixpoint the pmax merge reached.

    The §4.2 observed accounting runs per site on the device's
    ``pending`` stream: every product state enters each device's pending
    exactly once, and a (group, node) dedup bitmap keeps the §4.2.2
    broadcast-cache semantics, so the converged meters equal the
    merged-frontier meters bit-for-bit — the executor returns the true
    per-site breakdown ``d_s2_sites`` (n_sites, B) alongside the psum'd
    total, instead of the global backend's ``replication_factor``
    approximation.

    The start batch is sharded over ``batch_axis`` (as in the reference
    backend): each batch shard runs its own q_pad-chunked fixpoints
    against the full (replicated-over-batch) site tiles.

    Under ``semantics="witness"`` each device stamps discovery levels on
    its own (ring-iteration) clock, and the final plane is ``pmin``-ed
    over the site axes.  Ring-iteration levels are not BFS levels, but
    they stay *valid* for strict-decrease reconstruction: at the device
    achieving a pair's minimum level the discovery was local (a
    ring-delivered discovery implies a neighbor with a smaller level,
    contradicting minimality), so a strictly-smaller-level product
    predecessor exists among that device's edges ⊆ global edges.  The
    levels output rides LAST, after ``d_s2_sites``.
    """
    from repro.kernels.frontier import frontier as fkernel
    from repro.kernels.frontier import ops as fops

    if placement is None:
        raise ValueError(
            "backend='frontier_kernel_sharded' requires placement= (the site partition)"
        )
    if placement.graph.n_nodes != n_nodes:
        raise ValueError(
            f"placement has {placement.graph.n_nodes} nodes, executor built for {n_nodes}"
        )
    axis_size = 1
    for ax in site_axes:
        axis_size *= int(mesh.shape[ax])
    if placement.n_sites % axis_size:
        raise ValueError(
            f"n_sites={placement.n_sites} must be divisible by the site-axis "
            f"size {axis_size} (sites are blocked over {site_axes})"
        )
    interpret = fkernel.resolve_interpret(interpret)
    if bucket_floor is None:
        bucket_floor = fops.BUCKET_FLOOR
    stack_sharding = site_sharding(mesh, site_axes)
    if plan_store is not None:
        site_graphs = plan_store.local_graphs(placement, epoch=stats_epoch)
        exec_staged = plan_store.staged_merged(
            placement, block_size, axis_size, epoch=stats_epoch, tile_dtype=tile_dtype
        )
        tile_buckets = plan_store.tile_buckets(
            placement, block_size, axis_size, epoch=stats_epoch, floor=bucket_floor,
            tile_dtype=tile_dtype, sharding=stack_sharding,
        )
    else:
        site_graphs = [placement.local_graph(s) for s in range(placement.n_sites)]
        staged = fops.stage_sharded_graph(site_graphs, block_size, tile_dtype)
        exec_staged = fops.merge_staged_sites(staged, axis_size)
        tile_buckets = fops.bucket_staged_sites(
            exec_staged, axis_size, bucket_floor, stack_sharding
        )
    plan = fops.build_sharded_level_schedule(
        ca, exec_staged, tile_buckets, axis_size=axis_size, bucket_floor=bucket_floor
    )
    if plan_store is not None:
        plan_store.record_plan_pad_waste(plan)
    n_states, q_pad, v_pad = ca.n_states, plan.q_pad, plan.v_pad
    union_members = plan.union_members
    witness = semantics == "witness"
    levels = max_levels if max_levels is not None else n_states * n_nodes
    # a discovery may need up to axis_size ring hops to reach the site
    # holding the next edge, so the iteration budget scales accordingly
    levels = levels * axis_size if axis_size > 1 else levels
    n_buckets = len(plan.buckets)

    sgroups = symbol_set_groups(ca)
    n_groups = max(len(sgroups), 1)
    label_deg = (
        plan_store.label_degrees(
            placement, site_graphs, placement.graph.n_labels, v_pad, epoch=stats_epoch
        )
        if plan_store is not None
        else None
    )
    deg, payloads = _site_symbol_degrees(sgroups, site_graphs, v_pad, label_deg)
    pay_c = jnp.asarray(payloads)
    state_rows = [jnp.asarray(states, jnp.int32) for _, states in sgroups]

    def local(*ops):
        # ops = 8 arrays per bucket (leading dim = this device's member
        # sites of that bucket), then deg_l, starts
        bucket_ops = [ops[i * 8 : (i + 1) * 8] for i in range(n_buckets)]
        deg_l, starts = ops[-2], ops[-1]
        s_local = deg_l.shape[0]

        def expand(frontier):  # (n_states * q_pad, v_pad) -> same, {0,1}
            fre = fops.extend_frontier(frontier, union_members, n_states, q_pad)
            merged = jnp.zeros((n_states * q_pad, v_pad), jnp.float32)
            for tiles, fi, vl, ti, fr, fc, orw, oc in bucket_ops:
                if tiles.shape[0] == 1:
                    counts = fkernel.fused_level_blocks(
                        fre, tiles[0], fi[0], vl[0], ti[0], fr[0], fc[0],
                        orw[0], oc[0], plan.block_size, q_pad,
                        interpret=interpret, n_out_rows=n_states * q_pad,
                    )
                else:  # all of this bucket's local sites in ONE vmapped call
                    counts = jax.vmap(
                        lambda t, fi_, vl_, ti_, fr_, fc_, orw_, oc_: (
                            fkernel.fused_level_blocks(
                                fre, t, fi_, vl_, ti_, fr_, fc_, orw_, oc_,
                                plan.block_size, q_pad, interpret=interpret,
                                n_out_rows=n_states * q_pad,
                            )
                        )
                    )(tiles, fi, vl, ti, fr, fc, orw, oc).max(axis=0)
                merged = jnp.maximum(merged, counts)
            return jnp.minimum(merged, 1.0)

        def fixpoint(flat0):  # (n_states * q_pad, v_pad) f32 0/1
            zero_q = jnp.zeros((q_pad,), jnp.float32)

            def cond(state):
                # collective-free: `active` was psum-agreed in the body
                active, lev = state[3], state[2]
                return jnp.logical_and(active, lev < levels)

            def body(state):
                visited, pending, lev, _, buf, done, q_bc, d_site, n_bc = state[:9]
                fr3 = pending.reshape(n_states, q_pad, v_pad)
                # §4.2 meters on this device's pending stream: every
                # product state enters pending exactly once per device
                # (the `done` bitmap dedups (group, node) pairs), so the
                # converged totals match the merged-frontier meters
                new_done = []
                for gi, rows in enumerate(state_rows):
                    now_g = fr3[rows].max(axis=0)  # (q_pad, v_pad)
                    new_g = now_g * (1.0 - done[gi])
                    cnt = new_g.sum(axis=1)
                    q_bc = q_bc + pay_c[gi] * cnt
                    n_bc = n_bc + cnt
                    d_site = d_site + EDGE_SYMBOLS * jnp.einsum(
                        "qv,sv->sq", new_g, deg_l[:, gi]
                    )
                    new_done.append(jnp.maximum(done[gi], now_g))
                done = jnp.stack(new_done) if new_done else done
                # local expansion over the shape buckets, overlapped with
                # the ring forward of last iteration's discoveries (the
                # ppermute reads `buf`, not `mine` — no data dependence)
                mine = expand(pending)
                incoming = mine
                if axis_size > 1:
                    # one hop per axis, each reading the ORIGINAL buf (a
                    # sequential composition would shift diagonally and
                    # miss devices on a multi-axis torus)
                    for ax in site_axes:
                        n_ax = int(mesh.shape[ax])
                        if n_ax > 1:
                            ring = jax.lax.ppermute(
                                buf, ax, [(i, (i + 1) % n_ax) for i in range(n_ax)]
                            )
                            incoming = jnp.maximum(incoming, ring)
                new = incoming * (1.0 - visited)  # exact on {0,1} floats
                active = (new > 0).any()
                if axis_size > 1:
                    # agree `active` over EVERY mesh axis, not just
                    # site_axes: the ring ppermute rendezvouses all
                    # devices, so batch shards must run identical trip
                    # counts (extra iterations on a converged shard are
                    # no-ops: new stays zero).  Without a ring the body
                    # is collective-free and shards exit independently.
                    for ax in mesh.axis_names:
                        if int(mesh.shape[ax]) > 1:
                            active = jax.lax.psum(active.astype(jnp.int32), ax) > 0
                out = (
                    jnp.maximum(visited, new), new, lev + 1, active, new,
                    done, q_bc, d_site, n_bc,
                )
                if witness:
                    # this device's clock: ring-delivered discoveries
                    # stamp the iteration they arrived, pmin'd at the end
                    levmap = jnp.where(
                        new > 0, lev.astype(jnp.float32) + 2.0, state[9]
                    )
                    out = out + (levmap,)
                return out

            state = (
                flat0, flat0, jnp.int32(0), jnp.asarray(True),
                jnp.zeros_like(flat0),
                jnp.zeros((n_groups, q_pad, v_pad), jnp.float32),
                zero_q, jnp.zeros((s_local, q_pad), jnp.float32), zero_q,
            )
            if witness:
                state = state + (jnp.where(flat0 > 0, 1.0, INF_LEVEL),)
            final = jax.lax.while_loop(cond, body, state)
            visited, q_bc, d_site, n_bc = final[0], final[6], final[7], final[8]
            vis3 = visited.reshape(n_states, q_pad, v_pad)
            acc = jnp.zeros((q_pad, v_pad), jnp.float32)
            for qf in ca.accepting:
                acc = jnp.maximum(acc, vis3[qf])
            out = (acc[:, :n_nodes] > 0, q_bc, d_site, n_bc)
            if witness:
                levmap = final[9]
                for ax in site_axes:
                    if int(mesh.shape[ax]) > 1:
                        levmap = jax.lax.pmin(levmap, ax)
                lev3 = levmap.reshape(n_states, q_pad, v_pad)
                out = out + (lev3.transpose(1, 0, 2)[:, :, :n_nodes],)
            return out

        b = starts.shape[0]
        n_chunks = -(-b // q_pad)
        pad = n_chunks * q_pad - b
        if pad:
            starts = jnp.concatenate([starts, jnp.zeros((pad,), starts.dtype)])
        chunks = starts.reshape(n_chunks, q_pad)

        def one_chunk(schunk):
            f0 = (
                jnp.zeros((n_states, q_pad, v_pad), jnp.float32)
                .at[ca.start, jnp.arange(q_pad), schunk]
                .set(1.0)
            )
            return fixpoint(f0.reshape(n_states * q_pad, v_pad))

        out = jax.lax.map(one_chunk, chunks)
        acc, q_bc, d_site, n_bc = out[:4]
        # d_site: (n_chunks, s_local, q_pad) -> (s_local, B)
        d_site = d_site.transpose(1, 0, 2).reshape(s_local, n_chunks * q_pad)[:, :b]
        d_total = d_site.sum(axis=0)
        for ax in site_axes:
            d_total = jax.lax.psum(d_total, ax)
        res = (
            acc.reshape(n_chunks * q_pad, n_nodes)[:b],
            q_bc.reshape(-1)[:b],
            d_total,
            n_bc.reshape(-1)[:b].astype(jnp.int32),
            d_site,
        )
        if witness:
            res = res + (
                out[4].reshape(n_chunks * q_pad, n_states, n_nodes)[:b],
            )
        return res

    spec_s = lambda extra: P(site_axes, *([None] * extra))  # noqa: E731
    b_ax = batch_axis if batch_axis and batch_axis in mesh.axis_names else None
    spec_b = P(b_ax) if b_ax else P()
    bucket_args, bucket_specs = [], []
    for bk in plan.buckets:
        bucket_args += [
            bk.tiles, bk.firsts, bk.valids, bk.tile_ids,
            bk.f_rows, bk.f_cols, bk.o_rows, bk.o_cols,
        ]
        # tiles (rows, n_tiles, B, B); step arrays (rows, n_steps) — rows
        # is device-major, so sharding it over site_axes hands each
        # device exactly its member sites of this bucket
        bucket_specs += [spec_s(3)] + [spec_s(1)] * 7
    out_specs = (
        P(b_ax, None) if b_ax else P(None, None),
        spec_b, spec_b, spec_b,
        P(site_axes, b_ax),  # per-site × per-query response meters
    )
    if witness:
        out_specs = out_specs + (
            P(b_ax, None, None) if b_ax else P(None, None, None),
        )
    operand_specs = (*bucket_specs, spec_s(2))  # + deg (n_sites, n_groups, v_pad)
    sharded = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            *operand_specs,
            spec_b,  # starts: sharded over the batch axis, every site sees
            # its batch shard's full frontier (the broadcast half)
        ),
        out_specs=out_specs,
        check_vma=False,
    )
    # each site's slab and schedule live on the device that runs its
    # site, so a call moves only the start batch
    operands = jax.device_put(
        (*bucket_args, deg),
        tuple(NamedSharding(mesh, spec) for spec in operand_specs),
    )

    def run(operands, starts):
        return sharded(*operands, starts)

    # retrieval runs on the staged per-site tiles
    return _bind_operands(run, operands, interpret)


def s2_execute(
    mesh: Mesh,
    placement: Placement,
    ca: CompiledAutomaton,
    start_nodes: np.ndarray,
    site_axes: tuple[str, ...] = ("data",),
    batch_axis: str | None = "model",
    max_levels: int | None = None,
    step_fn=None,
    device_arrays: dict | None = None,
    backend: str = "reference",
    block_size: int = 128,
    interpret: bool | None = None,
    plan_store=None,
    stats_epoch: int = 0,
    bucket_floor: int | None = None,
    semantics: str = "pairs",
    tile_dtype: str = "f32",
    tile_store_budget_bytes: int | None = None,
) -> tuple[np.ndarray, list[StrategyCost]] | tuple[
    np.ndarray, list[StrategyCost], np.ndarray
]:
    """Run the batched S2 executor for ``start_nodes``.

    Returns ``(answers, costs)``: answers (B, V) bool, plus one *observed*
    :class:`StrategyCost` per start node, measured by the executor itself
    (the feedback signal ``repro.serve`` closes the §5 estimation loop
    with).  Unicast symbols are converted back to the meters' single-copy
    convention by dividing the summed per-site responses by the placement's
    replication factor K (an average — per-query matched-edge replication
    may deviate slightly).

    Under ``semantics="witness"`` (the ``step_fn``, if prebuilt, must
    have been built with the same semantics) the return is a 3-tuple
    ``(answers, costs, levels)`` with levels (B, n_states, n_nodes) f32
    discovery levels — feed them to
    :func:`repro.core.witness.reconstruct_path`.

    ``step_fn`` accepts a prebuilt executor from :func:`make_s2_step_fn`
    (e.g. from the serve layer's executor cache) so repeated query classes
    do not re-trace; it must have been built for a compatible
    (automaton signature, n_nodes, mesh) triple.  ``device_arrays``
    accepts the placement's (already staged) padded site arrays so a
    serving loop does not rebuild them per call.

    The site-sharded backend's step functions return a fifth output —
    the per-site response breakdown — which lands on each cost's
    ``site_unicast_symbols`` (true per-site §4.2 retrieval counts; their
    sum is the K-weighted total the other backends approximate).

    ``plan_store`` (a :class:`~repro.core.plans.GraphPlanStore`) routes
    every graph-dependent artifact through the shared Stage-A cache: the
    reference backend's padded site arrays here, and — when ``step_fn``
    is not prebuilt — the fused backends' staged tiles inside
    :func:`make_s2_step_fn`.
    """
    if device_arrays is not None:
        arrays = device_arrays
    elif step_fn is None and backend in (
        "frontier_kernel", "frontier_kernel_packed", "frontier_kernel_sharded"
    ):
        # the fused backends read only their staged tile plans; skip the
        # O(n_sites × max_edges) packing + transfer of unused site arrays
        arrays = {
            k: np.zeros((1, 1), bool if k == "mask" else np.int32)
            for k in ("src", "lbl", "dst", "mask")
        }
    elif plan_store is not None:
        arrays = plan_store.site_device_arrays(
            placement, epoch=stats_epoch, sharding=site_sharding(mesh, site_axes)
        )
    else:
        arrays = placement.padded_device_arrays()
    if step_fn is None:
        step_fn = make_s2_step_fn(
            ca, placement.graph.n_nodes, mesh, site_axes, batch_axis, max_levels,
            backend=backend, graph=placement.graph,
            replication_factor=placement.replication_factor,
            block_size=block_size, interpret=interpret, placement=placement,
            plan_store=plan_store, stats_epoch=stats_epoch,
            bucket_floor=bucket_floor, semantics=semantics,
            tile_dtype=tile_dtype,
            tile_store_budget_bytes=tile_store_budget_bytes,
        )
    with spans.span("s2.dispatch"):
        out = step_fn(
            jnp.asarray(arrays["src"]),
            jnp.asarray(arrays["lbl"]),
            jnp.asarray(arrays["dst"]),
            jnp.asarray(arrays["mask"]),
            jnp.asarray(np.asarray(start_nodes, np.int32)),
        )
    kernel_bytes = getattr(step_fn, "kernel_bytes", None)
    if kernel_bytes is not None:  # the level-kernel call count comes last
        out, n_calls = out[:-1], out[-1]
    with spans.span("s2.fetch") as sp:
        acc, q_bc, d_s2, n_bc = (np.asarray(a) for a in out[:4])
        extras = out[4:]
        levels = None
        if semantics == "witness":
            # the levels plane is always the LAST extra output
            levels = np.asarray(extras[-1])  # (B, n_states, n_nodes)
            extras = extras[:-1]
        d_sites = np.asarray(extras[0]) if extras else None  # (n_sites, B)
        if sp:
            sp.count("answer_bytes", acc.nbytes + (levels.nbytes if levels is not None else 0))
            if kernel_bytes is not None:
                sp.count("levels", int(n_calls))
                sp.count("kernel_bytes", kernel_bytes)
    k_rep = max(placement.replication_factor, 1e-9)
    costs = [
        StrategyCost(
            strategy="S2",
            broadcast_symbols=float(q_bc[i]),
            unicast_symbols=float(d_s2[i]) / k_rep,
            n_broadcasts=int(n_bc[i]),
            edges_retrieved=int(round(float(d_s2[i]) / (EDGE_SYMBOLS * k_rep))),
            site_unicast_symbols=(
                tuple(float(x) for x in d_sites[:, i]) if d_sites is not None else ()
            ),
        )
        for i in range(len(q_bc))
    ]
    if semantics == "witness":
        return acc, costs, levels
    return acc, costs
