"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Nothing runs: each case lowers a frontier kernel (or the jitted fixpoint
around it) at deployment widths — block B=128, q_pad=8, the Alibaba
twin's v_pad — and compiles it for one chip of a ``v5e:2x2`` topology
described in this process.  The TPU compiler refuses what interpret mode
accepts (unsupported primitives, unaligned slices, VMEM overuse), so
these cases guard the kernels' chip path without a chip.

The topology is described inside a module fixture, never at import:
only the worker that runs this file loads the TPU compiler library.
The persistent compilation cache is off around the compiles — an entry
written for a described device cannot be read back here.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier import ops
from repro.kernels.frontier.frontier import fused_level_blocks, packed_level_blocks
from repro.kernels.frontier.ref import tile_words

B = 128
Q_PAD = ops.QPAD
N_STATES = 3
V_PAD = -(-50_000 // B) * B  # generators.alibaba_like() default node count
N_TILES = 4096
N_STEPS = 8192
N_SITE_ROWS = 2  # sites stacked in one shape bucket (the vmapped call)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tiles(tile_dtype, sharding, lead=()):
    width = B if tile_dtype == "f32" else tile_words(B)
    dtype = jnp.float32 if tile_dtype == "f32" else jnp.uint32
    return _sds((*lead, N_TILES, B, width), dtype, sharding)


def _schedule(sharding, lead=()):
    """firsts, valids, tile_ids, f_rows, f_cols, o_rows, o_cols."""
    return tuple(_sds((*lead, N_STEPS), jnp.int32, sharding) for _ in range(7))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


LEVEL_KERNELS = {
    "fused": (fused_level_blocks, jnp.float32),
    "packed": (packed_level_blocks, jnp.uint32),
}


@pytest.mark.parametrize("tile_dtype", ["f32", "uint32"])
@pytest.mark.parametrize("kernel", sorted(LEVEL_KERNELS))
def test_level_kernel_compiles_for_v5e(one_chip, kernel, tile_dtype):
    level, frontier_dtype = LEVEL_KERNELS[kernel]

    def step(frontier, tiles, *schedule):
        return level(
            frontier, tiles, *schedule, B, Q_PAD, interpret=False,
            n_out_rows=N_STATES * Q_PAD,
        )

    frontier = _sds(((N_STATES + 1) * Q_PAD, V_PAD), frontier_dtype, one_chip)
    compiled = (
        jax.jit(step)
        .lower(frontier, _tiles(tile_dtype, one_chip), *_schedule(one_chip))
        .compile()
    )
    _assert_kernel(compiled)


@pytest.mark.parametrize("tile_dtype", ["f32", "uint32"])
def test_vmapped_fused_kernel_compiles_for_v5e(one_chip, tile_dtype):
    """The sharded backend's per-bucket call: one fused level vmapped
    over the bucket's stacked site slabs and schedules."""

    def step(frontier, tiles, *schedule):
        per_site = jax.vmap(
            lambda t, *s: fused_level_blocks(
                frontier, t, *s, B, Q_PAD, interpret=False,
                n_out_rows=N_STATES * Q_PAD,
            )
        )
        return per_site(tiles, *schedule).max(axis=0)

    frontier = _sds((N_STATES * Q_PAD, V_PAD), jnp.float32, one_chip)
    lead = (N_SITE_ROWS,)
    compiled = (
        jax.jit(step)
        .lower(frontier, _tiles(tile_dtype, one_chip, lead), *_schedule(one_chip, lead))
        .compile()
    )
    _assert_kernel(compiled)


def test_reach_fixpoint_compiles_for_v5e(one_chip):
    """The device-resident fixpoint: a while loop over fused levels at
    the twin's v_pad, one union row, uint32 tiles."""
    frontier0 = _sds((N_STATES * Q_PAD, V_PAD), jnp.float32, one_chip)
    compiled = ops._reach_fixpoint.lower(
        frontier0, _tiles("uint32", one_chip), *_schedule(one_chip),
        block_size=B, q_pad=Q_PAD, max_levels=64, interpret=False,
        union_members=((0, 1),), n_states=N_STATES,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "while" in text
