"""Run one benchmark cell as ``bench/run.py`` does, with the program's span
recorder (``repro.spans``) on, and split the window by the program's
spans.

    python bench/split.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 1`` the run is ``run.py``'s traced run, and its trace is
also reduced by ``yardstick/spantrace.py``: the device's idle time by the
innermost ``bench.`` or ``rpq.`` span.  With ``--trace 0`` it is the
untraced run with the recorder on: its end-to-end metrics beside
``run.py``'s give what the recorder costs when it records.  Either way
the window's records are summed per span name (count, total, mean and
self milliseconds, counters).  The result line is printed as ``run.py``
prints it; the split goes to standard error and, as JSON, to
``chiprun_out/split/<cell>.<seed>.<trace>.json``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
OUT_DIR = os.path.join(ROOT, "chiprun_out", "split")


def summarize(records, t_open: float, t_close: float) -> dict:
    """Per span name, over the records ending in the window: how many,
    total, mean and self milliseconds (self: less the time of the spans
    opened inside it), and the counters summed."""
    inside = [r for r in records if t_open <= r.t1 <= t_close]
    child_s: dict[int, float] = defaultdict(float)
    for r in records:
        if r.parent is not None:
            child_s[r.parent] += r.t1 - r.t0
    out: dict[str, dict] = {}
    for r in inside:
        s = out.setdefault(r.name, {"n": 0, "total_ms": 0.0, "self_ms": 0.0, "counters": {}})
        s["n"] += 1
        s["total_ms"] += 1e3 * (r.t1 - r.t0)
        s["self_ms"] += 1e3 * (r.t1 - r.t0 - child_s[r.id])
        for k, v in r.counters.items():
            if isinstance(v, (int, float)):
                s["counters"][k] = s["counters"].get(k, 0) + v
    for s in out.values():
        s["mean_ms"] = s["total_ms"] / s["n"]
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["total_ms"]))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    from repro import spans
    from yardstick import harness, spantrace, tracing

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    seen: dict = {"records": []}

    # keep what the run's readers drain, the window's open, and the split
    # of the trace before the harness deletes it
    drain = spans.drain

    def keep_drain():
        out = drain()
        seen["records"].extend(out)
        return out

    spans.drain = keep_drain
    serve = harness.serve

    async def keep_open(*a, **k):
        out = await serve(*a, **k)
        seen["t_open"] = out[1]
        return out

    harness.serve = keep_open
    reduce = tracing.reduce

    def reduce_and_split(path, *a, **k):
        seen["split"] = spantrace.reduce(path)
        return reduce(path, *a, **k)

    tracing.reduce = reduce_and_split
    if not args.trace:  # a traced run's readers turn the recorder on
        spans.enable()
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench/split.py: {e}; not running", file=sys.stderr)
        return 2
    finally:
        spans.disable()
    keep_drain()

    t_open = seen["t_open"]
    split = {"workload": args.workload, "seed": args.seed, "trace": args.trace,
             "spans": summarize(seen["records"], t_open, t_open + args.seconds)}
    if "split" in seen:
        r = seen["split"]
        split.update(window_s=r.window_s, busy_s=r.busy_s, idle_s=r.window_s - r.busy_s,
                     idle_by_span=sorted(r.gaps_by_span.items(), key=lambda kv: -kv[1]),
                     longest_gaps=r.longest_gaps,
                     device_ops=sorted(r.op_s.items(), key=lambda kv: -kv[1])[:12])
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{args.workload}.{args.seed}.{args.trace}.json")
    with open(path, "w") as f:
        json.dump(split, f, indent=1)
    print(json.dumps({"split": split}), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
