"""The trace reduction of ``tracing.reduce``, with the device's idle gaps
put down to the innermost host span among the harness's ``bench.``
annotations *and* the program's own ``rpq.`` spans (``repro.spans``).

``tracing.reduce`` reads ``bench.`` annotations only; the program's
spans sit inside them (``rpq.flush`` inside ``bench.flush``), so here a
gap goes to the shortest span of either kind that covers its middle,
and span names keep their prefix.  Busy time, op times and the window
are computed as there, from the same helpers (``bench/split.py`` prints
the split of a run).
"""

from __future__ import annotations

from collections import defaultdict

import numpy as np

from yardstick import tracing

PREFIXES = ("bench.", "rpq.")
MARK = "bench." + tracing.WINDOW_SPAN  # the harness's annotation around the window
NONE = "host: none"


def read_events(path: str, prefixes: tuple[str, ...] = PREFIXES):
    """The trace's device operations by plane (start, end, label; the
    label ``None`` for a container op), its host spans among
    ``prefixes`` and the window mark (start, end, full name), and the
    first and last event times, all in ns on the trace's clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: dict[str, list[tuple[int, int, str | None]]] = {}
    host_spans: list[tuple[int, int, str]] = []
    lo = hi = None
    for plane in pd.planes:
        is_device = bool(tracing.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name != tracing.OPS_LINE:
                continue
            for ev in line.events:
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                lo = a if lo is None else min(lo, a)
                hi = b if hi is None else max(hi, b)
                if is_device:
                    label, opcode = tracing.op_label(ev.name)
                    device_ops.setdefault(plane.name, []).append(
                        (a, b, label if opcode not in tracing.CONTAINERS else None)
                    )
                elif ev.name == MARK or ev.name.startswith(prefixes):
                    host_spans.append((a, b, ev.name))
    return device_ops, host_spans, lo, hi


def reduce(path: str, prefixes: tuple[str, ...] = PREFIXES,
           window: tuple[int, int] | None = None) -> tracing.Reduced:
    """As ``tracing.reduce``, with ``gaps_by_span`` keyed by the full
    annotation name of the innermost span among ``prefixes``."""
    device_ops, host_spans, lo, hi = read_events(path, prefixes)
    marks = [s for s in host_spans if s[2] == MARK]
    host_spans = [s for s in host_spans if s[2] != MARK]
    if window is None:
        window = (marks[0][0], marks[0][1]) if marks else (lo or 0, hi or 0)
    w0, w1 = window

    op_s: dict[str, float] = defaultdict(float)
    busy = []
    unions = {}
    for plane, evs in sorted(device_ops.items()):
        clipped = [(max(a, w0), min(b, w1), n) for a, b, n in evs if b > w0 and a < w1]
        for a, b, n in clipped:
            if n is not None:
                op_s[n] += (b - a) / 1e9
        unions[plane] = tracing._union([(a, b) for a, b, _ in clipped])
        busy.append(sum(b - a for a, b in unions[plane]) / 1e9)

    gaps_by_span: dict[str, float] = defaultdict(float)
    longest: list[tuple[str, float]] = []
    if unions:
        first = np.asarray(unions[sorted(unions)[0]], np.int64).reshape(-1, 2)
        starts = np.concatenate([[w0], first[:, 1]])
        ends = np.concatenate([first[:, 0], [w1]])
        keep = ends > starts
        starts, ends = starts[keep], ends[keep]
        names = innermost(host_spans, (starts + ends) // 2)
        secs = (ends - starts) / 1e9
        for name, sec in zip(names, secs):
            gaps_by_span[name] += float(sec)
        order = np.argsort(-secs)[:10]
        longest = [(str(names[i]), float(secs[i])) for i in order]
    return tracing.Reduced(
        window_s=max(w1 - w0, 0) / 1e9,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        n_devices=len(busy),
        op_s=dict(op_s),
        gaps_by_span=dict(gaps_by_span),
        longest_gaps=longest,
    )


def innermost(host_spans: list[tuple[int, int, str]], points: np.ndarray) -> np.ndarray:
    """For each point, the name of the shortest span covering it (the
    intervals of one name merged first), or ``"host: none"``."""
    best = np.full(len(points), np.iinfo(np.int64).max)
    names = np.full(len(points), NONE, dtype=object)
    for name in sorted({n for _, _, n in host_spans}):
        iv = np.asarray(tracing._union([(a, b) for a, b, n in host_spans if n == name]), np.int64)
        k = np.searchsorted(iv[:, 0], points, side="right") - 1
        inside = (k >= 0) & (points <= iv[np.maximum(k, 0), 1])
        length = np.where(inside, iv[np.maximum(k, 0), 1] - iv[np.maximum(k, 0), 0], best)
        better = inside & (length < best)
        best = np.where(better, length, best)
        names[better] = name
    return names

