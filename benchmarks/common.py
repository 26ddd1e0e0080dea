"""Shared benchmark fixtures: the Alibaba statistical twin + indexes,
built once and cached across benchmark modules, plus the execution-
environment header every ``BENCH_*.json`` carries (so interpret-mode CPU
numbers are never silently presented as kernel numbers)."""

from __future__ import annotations

import functools
import time

import jax

from repro.core import paa
from repro.graph.generators import alibaba_like
from repro.kernels.frontier.frontier import resolve_interpret

# free-form provenance note threaded through `benchmarks.run --platform`
# (e.g. "ci-cpu-skylake", "v5p-8 pod slice"); lands in every BENCH json
PLATFORM_NOTE: str | None = None


def set_platform_note(note: str | None) -> None:
    global PLATFORM_NOTE
    PLATFORM_NOTE = note


def bench_env() -> dict:
    """The stable env header of every ``BENCH_*.json``: which XLA
    backend and device actually executed, how many devices, whether the
    Pallas kernels ran in interpret mode (the flag the kernels resolve,
    :func:`repro.kernels.frontier.frontier.resolve_interpret`; off-TPU
    it is always on — those latencies are interpreter numbers, not
    kernel numbers), and the operator-supplied platform note."""
    devices = jax.devices()
    return {
        "jax_backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "interpret": resolve_interpret(),
        "platform_note": PLATFORM_NOTE,
    }


@functools.lru_cache(maxsize=1)
def twin():
    g = alibaba_like()
    return g


@functools.lru_cache(maxsize=1)
def twin_index():
    return paa.HostIndex(twin())


@functools.lru_cache(maxsize=1)
def twin_device():
    return paa.device_form(twin())


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6  # µs
