"""Pallas TPU kernels: blocked boolean-semiring frontier expansion.

The PAA's per-transition work is F' |= F @ A_l where F is the (n_states ×
V) frontier and A_l the V×V adjacency of one label.  On TPU we tile V
into B×B blocks, store A_l block-sparse (only nonzero tiles), and OR-
accumulate per tile on the MXU: for each nonzero tile t with block row
r(t) and block col c(t):

    OUT[:, c(t)·B:(c(t)+1)·B]  |=  F[:, r(t)·B:(r(t)+1)·B] @ TILE(t)

Two grid layouts share this primitive:

* :func:`frontier_step_blocks` — ONE (transition, label) tile list per
  call; grid = one step per nonzero tile, tiles pre-sorted by block
  column so all writes to one output block are consecutive grid steps
  (the TPU-legal output-revisiting pattern).  This is the per-transition
  baseline: a BFS level costs one dispatch per transition × label entry.

* :func:`fused_level_blocks` — an ENTIRE BFS level over all transitions
  of the automaton in one call.  The frontier operand is
  (n_rows · q_pad, v_pad): row-block s < n_states is automaton state s,
  row-blocks past n_states are virtual *fan-in union rows* (the OR of
  several source states' frontiers, precomputed by the caller — see
  ``ops.extend_frontier``), and the q_pad (= 8, the f32 sublane minimum
  that a single-query kernel would waste) rows inside a block carry up
  to 8 independent queries' frontiers.  The grid concatenates every
  fan-in transition group's tile list, sorted by (dst_state, block_col);
  per-step scalar prefetch ids select the input row-block, the input
  col-block (tile block row), the tile, and the output (dst state, block
  col).  ``n_out_rows`` decouples the output height from the (extended)
  input height.  Dispatch count per level is exactly 1, independent of
  |transitions| and |labels|.

:func:`fused_level_blocks` also serves the site-sharded S2 backend: each
site runs it on a grid built from its *own* edge partition (bucketed
into power-of-two shape classes — see ``ops.build_sharded_level_plan``)
and the per-site outputs OR-merge across the site axis per level.

``valids`` is the in-kernel zero-step skip: a step with ``valids=0``
(a zero-tile cover step or a shape-class padding step) only runs the
``firsts`` zero-init predicate — it never issues the tile product, so
padding a schedule up to its bucket's power-of-two grid length costs a
predicate per step, not a tile pass.

Boolean OR is implemented as saturating add in f32 (counts then >0) —
MXU-native, exact for path-counting up to 2^24 (f32 integer range), and
the wrappers threshold back to {0,1}.

:func:`packed_level_blocks` is the **bitpacked** variant of the fused
level: the frontier operand is ``uint32`` *words* with queries packed
along the bit axis — the same 8-row tile minimum then carries 8 × 32 =
256 query lanes per automaton state — and the per-step tile product
becomes a bitwise OR-of-AND against the *same* staged f32 adjacency
tiles (converted to a boolean mask in-kernel, so Stage A stages tiles
once and serves both dtypes).  Bit-exact on the boolean semiring: word
bit q of ``out[r, j]`` is ``OR_v (f[r, v] bit q  AND  a[v, j])``.  The
scalar-prefetch schedule (``firsts`` zero-init, ``valids`` early-out,
sorted (o_row, o_col) steps) is shared verbatim with the f32 kernel.

Both entry points also accept a **bitpacked tile store**: when
``tiles`` is uint32 (n_tiles, B, ceil(B/32)) the dst axis is packed
into bit-planes (``ref.pack_blocks(tile_dtype="uint32")`` — the same
word layout as the frontier lanes) and the ``*_u32`` kernel variants
unpack each tile's bits in-register.  The f32-frontier variant then
runs the same MXU dot on the recovered {0,1} matrix; the packed-frontier
variant is pure bitwise AND/OR end to end — no in-kernel f32 threshold,
no popcounts — at 1/32 the tile-store HBM traffic per step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.extend.core import ClosedJaxpr, Jaxpr


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one place Pallas interpret mode is decided.  An explicit flag
    wins; ``None`` interprets exactly when JAX's default backend is not a
    TPU (the CPU test runs), so nothing on a TPU runs the interpreter
    unless a caller asks for it."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations in ``fn``'s jaxpr — the Pallas
    dispatch count of one call, robust to jit caching (pjit/while bodies
    are recursed into).  The fused-level acceptance test asserts this is
    1 per BFS level."""

    def _count(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for val in eqn.params.values():
                for v in val if isinstance(val, (tuple, list)) else (val,):
                    if isinstance(v, ClosedJaxpr):
                        n += _count(v.jaxpr)
                    elif isinstance(v, Jaxpr):
                        n += _count(v)
        return n

    return _count(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args).jaxpr)


def _frontier_kernel(rows_ref, cols_ref, f_ref, a_ref, o_ref):
    """One grid step: o[:, cols[i]] += f[:, rows[i]] @ a[i]."""
    i = pl.program_id(0)

    # first visit to this output block: zero it
    @pl.when(jnp.logical_or(i == 0, cols_ref[i] != cols_ref[jnp.maximum(i - 1, 0)]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    f = f_ref[...]  # (m_pad, B)
    a = a_ref[0]  # (B, B)
    o_ref[...] += jnp.dot(f, a, preferred_element_type=jnp.float32)


def frontier_step_blocks(
    frontier: jax.Array,  # (m_pad, V_pad) f32 0/1, m_pad multiple of 8
    tiles: jax.Array,  # (nnz, B, B) f32 0/1, sorted by block col
    block_rows: jax.Array,  # (nnz,) int32
    block_cols: jax.Array,  # (nnz,) int32, non-decreasing
    block_size: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns the raw count matrix (m_pad, V_pad); caller thresholds >0."""
    m_pad, v_pad = frontier.shape
    nnz = tiles.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nnz,),
        in_specs=[
            pl.BlockSpec((m_pad, block_size), lambda i, rows, cols: (0, rows[i])),
            pl.BlockSpec((1, block_size, block_size), lambda i, rows, cols: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m_pad, block_size), lambda i, rows, cols: (0, cols[i])),
    )
    return pl.pallas_call(
        _frontier_kernel,
        grid_spec=grid_spec,
        name="rpq_frontier_step",
        out_shape=jax.ShapeDtypeStruct((m_pad, v_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(block_rows, block_cols, frontier, tiles)


def _unpack_tile_bits(words: jax.Array, block_size: int) -> jax.Array:
    """In-kernel inverse of the ``tile_dtype="uint32"`` bit-plane packing:
    a (B, W) uint32 word block back to the (B, B) bool adjacency — dst
    ``d`` is bit ``d % 32`` of word ``d // 32``.  Pure VPU shifts on an
    iota, no gathers; the bit axis expands W words to W·32 columns and
    the slice drops the pad when B is not a multiple of 32."""
    b, w = words.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (b, w, 32), 2)
    bits = (words[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(b, w * 32)[:, :block_size] != 0


def _fused_level_kernel(
    firsts_ref, valids_ref, tids_ref, frows_ref, fcols_ref, orows_ref, ocols_ref,
    f_ref, a_ref, o_ref,
):
    """One grid step of the fused level:

        o[dst_state, :, ocol] += f[frow, :, fcol] @ tiles[tid]

    where the middle dim is the q_pad stacked-query rows and ``frow`` may
    address a virtual fan-in union row past the automaton states.
    ``firsts`` is precomputed on the host (steps are sorted by
    (dst_state, block_col), so the first step of each output block is
    known statically) — it gates the zero-init of the output block before
    accumulation.  ``valids`` gates the tile product itself: cover and
    shape-class padding steps (``valids=0``) early-out after the
    predicate instead of multiplying the zero tile."""
    i = pl.program_id(0)

    @pl.when(firsts_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valids_ref[i] == 1)
    def _accumulate():
        o_ref[...] += jnp.dot(f_ref[...], a_ref[0], preferred_element_type=jnp.float32)


def _fused_level_kernel_u32(
    firsts_ref, valids_ref, tids_ref, frows_ref, fcols_ref, orows_ref, ocols_ref,
    f_ref, a_ref, o_ref, *, block_size,
):
    """:func:`_fused_level_kernel` against a bitpacked uint32 tile store:
    the (1, B, W) word block unpacks to the (B, B) 0/1 adjacency
    in-register (:func:`_unpack_tile_bits`) and the accumulation is the
    same f32 MXU dot — counts and outputs are bit-exact vs the f32 tiles
    because both store exactly the same {0,1} adjacency."""
    i = pl.program_id(0)

    @pl.when(firsts_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valids_ref[i] == 1)
    def _accumulate():
        a = _unpack_tile_bits(a_ref[0], block_size).astype(jnp.float32)
        o_ref[...] += jnp.dot(f_ref[...], a, preferred_element_type=jnp.float32)


def fused_level_blocks(
    frontier: jax.Array,  # (n_rows * q_pad, v_pad) f32 0/1 (union rows appended)
    tiles: jax.Array,  # (n_tiles, B, B) f32 0/1; index 0 is the zero cover tile
    firsts: jax.Array,  # (n_steps,) int32 ∈ {0,1}: first visit to the output block
    valids: jax.Array,  # (n_steps,) int32 ∈ {0,1}: 0 = cover/padding, skip the dot
    tile_ids: jax.Array,  # (n_steps,) int32 into tiles
    f_rows: jax.Array,  # (n_steps,) int32: input row-block (state or union row)
    f_cols: jax.Array,  # (n_steps,) int32: input col-block = tile block row
    o_rows: jax.Array,  # (n_steps,) int32: output row-block = dst automaton state
    o_cols: jax.Array,  # (n_steps,) int32: output col-block = tile block col
    block_size: int,
    q_pad: int,
    interpret: bool | None = None,
    n_out_rows: int | None = None,  # output height; default = frontier height
) -> jax.Array:
    """One BFS level over ALL transitions in a single pallas_call.

    Steps must be sorted by (o_rows, o_cols) so each output block's
    writes are consecutive (the TPU output-revisiting rule), and the step
    list must cover every (dst_state, block_col) output block at least
    once (uncovered blocks are otherwise left undefined) — the plan
    builder appends zero-tile cover steps for that.  ``n_out_rows``
    (default: the frontier height) sets the output height independently
    of the input, which may carry extra fan-in union rows.  Returns the
    raw count matrix (n_out_rows, v_pad); callers threshold >0.

    ``tiles`` may be the f32 store (n_tiles, B, B) or the bitpacked
    uint32 store (n_tiles, B, ceil(B/32)) — the kernel variant is picked
    off the dtype and the packed tiles unpack in-register, so one
    Stage-B schedule serves both tile stores.
    """
    n_rows, v_pad = frontier.shape
    if n_out_rows is None:
        n_out_rows = n_rows
    n_steps = tile_ids.shape[0]
    packed_tiles = tiles.dtype == jnp.uint32
    kernel = (
        partial(_fused_level_kernel_u32, block_size=block_size)
        if packed_tiles
        else _fused_level_kernel
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec(
                (q_pad, block_size),
                lambda i, fi, vl, ti, fr, fc, orw, oc: (fr[i], fc[i]),
            ),
            pl.BlockSpec(
                (1, block_size, int(tiles.shape[2])),
                lambda i, fi, vl, ti, fr, fc, orw, oc: (ti[i], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (q_pad, block_size),
            lambda i, fi, vl, ti, fr, fc, orw, oc: (orw[i], oc[i]),
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="rpq_fused_level",
        out_shape=jax.ShapeDtypeStruct((n_out_rows, v_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(firsts, valids, tile_ids, f_rows, f_cols, o_rows, o_cols, frontier, tiles)


def _or_of_and(f: jax.Array, a: jax.Array) -> jax.Array:
    """Bitwise OR-of-AND of (q_pad, B) uint32 lane words ``f`` with a
    (B, B) bool adjacency ``a``: word bit q of ``out[r, j]`` is
    ``OR_v (f[r, v] bit q AND a[v, j])``.

    The select is laid out (v, r, j) — contraction axis leading — so the
    OR over v is a halving tree of elementwise ``|`` over whole (q_pad, B)
    slabs.  The TPU Pallas lowering has no bitwise reduction primitive
    (a generic ``lax.reduce`` with ``bitwise_or`` does not lower)."""
    c = jnp.where(a[:, None, :], f.T[:, :, None], jnp.uint32(0))
    while (n := c.shape[0]) > 1:
        h = n // 2
        folded = c[:h] | c[h : 2 * h]
        c = folded if n % 2 == 0 else jnp.concatenate([folded, c[2 * h :]])
    return c[0]


def _packed_level_kernel(
    firsts_ref, valids_ref, tids_ref, frows_ref, fcols_ref, orows_ref, ocols_ref,
    f_ref, a_ref, o_ref,
):
    """One grid step of the bitpacked fused level:

        o[dst_state, :, ocol] |= OR-of-AND(f[frow, :, fcol], tiles[tid])

    ``f_ref``/``o_ref`` are ``(q_pad, B)`` uint32 word blocks — bit q of
    a word is query lane ``row·32 + q``'s frontier bit for that node.
    The tile stays the staged f32 tensor; ``a != 0`` recovers the
    boolean adjacency in-kernel, so one Stage-A staging serves both the
    f32 matmul and the packed kernel.  The OR-of-AND is
    :func:`_or_of_and`: lane words masked by the adjacency, OR-folded
    over the contraction axis.  ``firsts`` /
    ``valids`` keep the exact semantics of :func:`_fused_level_kernel`:
    zero-init on the output block's first step, early-out on cover and
    shape-class padding steps."""
    i = pl.program_id(0)

    @pl.when(firsts_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valids_ref[i] == 1)
    def _accumulate():
        a = a_ref[0] != 0.0  # (B, B) bool — shared f32 staging
        o_ref[...] = o_ref[...] | _or_of_and(f_ref[...], a)


def _packed_level_kernel_u32(
    firsts_ref, valids_ref, tids_ref, frows_ref, fcols_ref, orows_ref, ocols_ref,
    f_ref, a_ref, o_ref, *, block_size,
):
    """The fully bitpacked inner step — packed frontier × packed tiles:
    both operands are uint32 words, the adjacency bit-plane unpacks to a
    bool mask in-register (:func:`_unpack_tile_bits`) and the product is
    the same :func:`_or_of_and` as :func:`_packed_level_kernel` — no f32
    threshold anywhere in the step, popcount-free boolean algebra on the
    VPU."""
    i = pl.program_id(0)

    @pl.when(firsts_ref[i] == 1)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(valids_ref[i] == 1)
    def _accumulate():
        a = _unpack_tile_bits(a_ref[0], block_size)  # (B, B) bool
        o_ref[...] = o_ref[...] | _or_of_and(f_ref[...], a)


def packed_level_blocks(
    frontier: jax.Array,  # (n_rows * q_pad, v_pad) uint32 lane words
    tiles: jax.Array,  # (n_tiles, B, B) f32 0/1 — the SAME Stage-A tensor
    firsts: jax.Array,  # (n_steps,) int32 ∈ {0,1}
    valids: jax.Array,  # (n_steps,) int32 ∈ {0,1}
    tile_ids: jax.Array,  # (n_steps,) int32 into tiles
    f_rows: jax.Array,  # (n_steps,) int32
    f_cols: jax.Array,  # (n_steps,) int32
    o_rows: jax.Array,  # (n_steps,) int32
    o_cols: jax.Array,  # (n_steps,) int32
    block_size: int,
    q_pad: int,
    interpret: bool | None = None,
    n_out_rows: int | None = None,
) -> jax.Array:
    """One bitpacked BFS level over ALL transitions in a single
    pallas_call — :func:`fused_level_blocks` with uint32 query-lane
    words instead of f32 rows (32× the lane density per row).

    Takes the SAME host-built schedule (``firsts``/``valids``/id arrays
    from ``ops.build_level_schedule``) and either tile store: the staged
    f32 tensor (thresholded to bool in-kernel) or the bitpacked uint32
    store (unpacked from bit-planes in-kernel — the packed×packed step
    is pure bitwise AND/OR, no f32 anywhere).  Returns the
    OR-accumulated word matrix (n_out_rows, v_pad) uint32 — already
    boolean per bit, no thresholding needed.
    """
    n_rows, v_pad = frontier.shape
    if n_out_rows is None:
        n_out_rows = n_rows
    n_steps = tile_ids.shape[0]
    packed_tiles = tiles.dtype == jnp.uint32
    kernel = (
        partial(_packed_level_kernel_u32, block_size=block_size)
        if packed_tiles
        else _packed_level_kernel
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec(
                (q_pad, block_size),
                lambda i, fi, vl, ti, fr, fc, orw, oc: (fr[i], fc[i]),
            ),
            pl.BlockSpec(
                (1, block_size, int(tiles.shape[2])),
                lambda i, fi, vl, ti, fr, fc, orw, oc: (ti[i], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (q_pad, block_size),
            lambda i, fi, vl, ti, fr, fc, orw, oc: (orw[i], oc[i]),
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="rpq_packed_level",
        out_shape=jax.ShapeDtypeStruct((n_out_rows, v_pad), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(firsts, valids, tile_ids, f_rows, f_cols, o_rows, o_cols, frontier, tiles)
