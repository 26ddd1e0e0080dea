"""The level kernel's share of the HBM roofline, in percent: the bytes
its calls moved in the window (each ``s2.fetch`` span's ``levels``,
the fixpoint's level-kernel calls, times its ``kernel_bytes``, the
bytes one call moves, as the packed executor counts them from its
Stage-B schedule) over the device seconds of those kernels in the trace
(``level_kernel_ms``'s ``KERNELS``) times the chip's HBM bandwidth in
``yardstick/peaks.json`` (``level_kernel_hbm_pct.<cell kind>``)."""

import jax

from yardstick import harness, program

KERNELS = harness.load_reader("level_kernel_ms").KERNELS


def read(obs):
    fetches = program.records(obs, "s2.fetch")
    if obs.trace is None or not fetches:
        return None
    moved = sum(r.counters.get("levels", 0) * r.counters.get("kernel_bytes", 0) for r in fetches)
    seconds = obs.trace.kernel_s(KERNELS)
    if moved <= 0 or seconds <= 0:
        return None
    peak = harness.peak_of(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / (seconds * peak)
