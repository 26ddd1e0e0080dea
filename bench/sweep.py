"""Find the highest rate an open-loop cell sustains, by one sweep.

    python bench/sweep.py --workload <cell> --seed <n> --rates 1,2,4,8 \
        --seconds 50 --out sweep.json
    python bench/sweep.py --apply sweep.json

The first form builds the cell's deployment once, then for each rate in
turn draws that rate's traffic (the mix's fixed stream, with arrival
times, SLO classes and tenants from ``--seed`` plus the rate's index,
or from the mix's ``schedule_seed`` where it states one),
warms it as a run does (its cold classes' plans are forgotten again, so
they meet the planner in the window at every rate), and serves one
window.  A rate keeps pace when every request is
answered (none refused or lost) and the backlog (requests due but not
yet answered) at the window's close is at most one second's worth of
arrivals and no larger than at the window's middle.  The knee is the
highest rate that keeps pace below the first that does not.

``--apply`` writes 0.8 x the knee into the cell's traffic file as
``rate_qps`` and prints the rates tried as a table for ``PERF.md``.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]

SHARE_OF_KNEE = 0.8


def _backlog(outcomes, t: float) -> int:
    return sum(1 for o in outcomes if o.t_due <= t and (o.t_done is None or o.t_done > t))


def keeps_pace(row: dict) -> bool:
    end, mid = row["backlog_close"], row["backlog_mid"]
    return (row["answered"] == row["requests"]
            and end <= max(row["rate_qps"], 1.0) and end <= max(mid, 1))


def knee(rows: list[dict]) -> float | None:
    found = None
    for row in rows:
        if not keeps_pace(row):
            break
        found = row["rate_qps"]
    return found


def sweep(workload: str, seed: int, rates: list[float], seconds: float) -> dict:
    import jax
    import numpy as np

    from repro.dist import compat
    from yardstick import harness, traffic

    harness.enable_compile_cache()

    cell = harness.load_cell(workload)
    if cell.mix["loop"] != "open":
        raise SystemExit(f"{workload} is not an open-loop cell")
    devices = harness.check_devices(cell.chips)
    mesh = compat.make_mesh((cell.chips, 1), ("data", "model"), devices=devices[: cell.chips])
    world = harness.build_world(cell.config, mesh)
    rows = []
    for k, rate in enumerate(rates):
        mix = copy.deepcopy(cell.mix)
        mix["rate_qps"] = rate
        plan = traffic.build(mix, world.graph, world.ref, seed + k + 1, seconds)
        harness.warm_up(world.service, world.aio_config, plan)
        outcomes, t_open, _ = asyncio.run(
            harness.serve(world.service, world.aio_config, plan, seconds)
        )
        lat = [(o.t_done - o.t_due) * 1e3 for o in outcomes if o.answers is not None]
        mid, end = _backlog(outcomes, t_open + seconds / 2), _backlog(outcomes, t_open + seconds)
        row = {
            "rate_qps": rate,
            "requests": len(outcomes),
            "answered": len(lat),
            "backlog_mid": mid,
            "backlog_close": end,
            "p50_ms": float(np.percentile(lat, 50)) if lat else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat else None,
        }
        row["keeps_pace"] = keeps_pace(row)
        print(json.dumps(row), file=sys.stderr, flush=True)
        rows.append(row)
    return {
        "workload": workload, "seed": seed, "seconds": seconds, "rows": rows, "knee_qps": knee(rows),
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
        "jax": jax.__version__,
    }


def apply(path: str) -> None:
    from yardstick import harness

    with open(path) as f:
        result = json.load(f)
    for row in result["rows"]:
        row["keeps_pace"] = keeps_pace(row)
    result["knee_qps"] = knee(result["rows"])
    if result["knee_qps"] is None:
        raise SystemExit("the sweep found no rate that keeps pace; nothing applied")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == result["workload"])
    mix_path = os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    mix = harness._load_json(mix_path)
    mix["rate_qps"] = round(SHARE_OF_KNEE * result["knee_qps"], 3)
    with open(mix_path, "w") as f:
        f.write(json.dumps(mix, indent=2) + "\n")
    print(f"{mix_path}: rate_qps = {mix['rate_qps']} ({SHARE_OF_KNEE} x knee {result['knee_qps']})")
    print("| rate (queries/s) | requests | answered | backlog at middle | backlog at close "
          "| p50 ms | p95 ms | keeps pace |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in result["rows"]:
        print(f"| {r['rate_qps']} | {r['requests']} | {r['answered']} | {r['backlog_mid']} "
              f"| {r['backlog_close']} | {r['p50_ms']} | {r['p95_ms']} | {r['keeps_pace']} |")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", default="1,2,4,8,16")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    ap.add_argument("--apply", metavar="SWEEP_JSON")
    args = ap.parse_args()
    if args.apply:
        apply(args.apply)
        return 0
    from yardstick import harness

    try:
        result = sweep(args.workload, args.seed, [float(r) for r in args.rates.split(",")],
                       args.seconds)
    except harness.NoChip as e:
        print(f"bench/sweep.py: {e}; not running", file=sys.stderr)
        return 2
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
