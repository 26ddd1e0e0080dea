"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* busy: per device plane, the union of the intervals in which an
  operation ran, clipped to the traced window; averaged over the chips.
* kernel time: the summed device durations of the operations whose name
  matches a pattern (the Pallas level kernels, for one).
* idle gaps: the gaps between busy intervals of the first device, each
  put down to the innermost ``bench.<span>`` host annotation that covers
  its middle (``host: none`` where the host was inside no span).

Reading needs nothing but JAX (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

import numpy as np

# device planes are named "/device:TPU:0", ...; the line that carries one
# event per executed operation is "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")  # ops whose time is their body's
TARGET = re.compile(r'custom_call_target="([^"]+)"')
SPAN_PREFIX = "bench."
WINDOW_SPAN = "window"  # the harness's annotation around the measured window


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over the device planes
    n_devices: int
    op_s: dict[str, float]  # op label -> summed device seconds (no containers)
    gaps_by_span: dict[str, float]  # host span name -> idle seconds
    longest_gaps: list[tuple[str, float]]

    def kernel_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}


def op_label(text: str) -> tuple[str, str]:
    """(label, opcode) of an "XLA Ops" event, whose name is the HLO
    instruction's text: ``%fusion.27 = u32[2,8]{...} fusion(...), ...``.
    The label is ``<opcode> <instruction>``, with a custom call's target
    after its opcode (``custom-call tpu_custom_call %body.6`` for a
    Pallas kernel)."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:80], ""
    i = 0
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    opcode = rest[i:].strip().split("(", 1)[0]
    target = TARGET.search(rest)
    if target:
        return f"{opcode} {target.group(1)} {name}", opcode
    return f"{opcode} {name}", opcode


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(path: str, window: tuple[int, int] | None = None) -> Reduced:
    """``window`` is (t0_ns, t1_ns) on the trace's clock; by default the
    ``bench.window`` annotation, or else the span from the first to the
    last event of any plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: dict[str, list[tuple[int, int, str]]] = {}
    host_spans: list[tuple[int, int, str]] = []
    lo, hi = None, None
    for plane in pd.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                lo = a if lo is None else min(lo, a)
                hi = b if hi is None else max(hi, b)
                if is_device:
                    label, opcode = op_label(ev.name)
                    device_ops.setdefault(plane.name, []).append(
                        (a, b, label if opcode not in CONTAINERS else None)
                    )
                elif ev.name.startswith(SPAN_PREFIX):
                    host_spans.append((a, b, ev.name[len(SPAN_PREFIX):]))
    marks = [s for s in host_spans if s[2] == WINDOW_SPAN]
    host_spans = [s for s in host_spans if s[2] != WINDOW_SPAN]
    if window is None:
        window = (marks[0][0], marks[0][1]) if marks else (lo or 0, hi or 0)
    w0, w1 = window
    window_s = max(w1 - w0, 0) / 1e9

    op_s: dict[str, float] = defaultdict(float)
    busy = []
    unions = {}
    for plane, evs in sorted(device_ops.items()):
        clipped = [(max(a, w0), min(b, w1), n) for a, b, n in evs if b > w0 and a < w1]
        for a, b, n in clipped:
            if n is not None:  # a container's time is its body's ops
                op_s[n] += (b - a) / 1e9
        unions[plane] = _union([(a, b) for a, b, _ in clipped])
        busy.append(sum(b - a for a, b in unions[plane]) / 1e9)

    gaps_by_span: dict[str, float] = defaultdict(float)
    longest: list[tuple[str, float]] = []
    if unions:
        first = np.asarray(unions[sorted(unions)[0]], np.int64).reshape(-1, 2)
        starts = np.concatenate([[w0], first[:, 1]])
        ends = np.concatenate([first[:, 0], [w1]])
        keep = ends > starts
        starts, ends = starts[keep], ends[keep]
        mids = (starts + ends) // 2
        # innermost span: of the spans covering a gap's middle, the shortest
        best_len = np.full(len(mids), np.iinfo(np.int64).max)
        names = np.full(len(mids), "host: none", dtype=object)
        for name in sorted({n for _, _, n in host_spans}):
            iv = np.asarray(_union([(a, b) for a, b, n in host_spans if n == name]), np.int64)
            k = np.searchsorted(iv[:, 0], mids, side="right") - 1
            inside = (k >= 0) & (mids <= iv[np.maximum(k, 0), 1])
            length = np.where(inside, iv[np.maximum(k, 0), 1] - iv[np.maximum(k, 0), 0], best_len)
            better = inside & (length < best_len)
            best_len = np.where(better, length, best_len)
            names[better] = name
        secs = (ends - starts) / 1e9
        for name, sec in zip(names, secs):
            gaps_by_span[name] += float(sec)
        order = np.argsort(-secs)[:10]
        longest = [(str(names[i]), float(secs[i])) for i in order]
    return Reduced(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        n_devices=len(busy),
        op_s=dict(op_s),
        gaps_by_span=dict(gaps_by_span),
        longest_gaps=longest[:10],
    )
