"""Benchmark driver — one module per paper table/figure, plus the serve
throughput benchmark.

Prints CSV rows ``name,...`` per artifact; see EXPERIMENTS.md for the
interpretation and paper-value comparisons.  The ``serve`` benchmark
additionally writes ``BENCH_serve.json`` (queries/sec, p50/p95 latency,
plan-cache hit rate) so the perf trajectory accumulates across PRs.

Run all:     PYTHONPATH=src python -m benchmarks.run
Run subset:  PYTHONPATH=src python -m benchmarks.run serve fig3
Regression:  PYTHONPATH=src python -m benchmarks.run dist --regress
             (re-runs the ``dist`` subset and exits non-zero if any
             fixpoint-ms metric regressed > REGRESS_FACTOR× vs the
             checked-in BENCH_frontier_sharded.json baseline)
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
import types

KNOWN = [
    "table1", "table2", "fig2", "fig3", "fig4", "scenario6", "roofline",
    "serve", "serve_async", "frontier", "dist", "plans", "packed",
    "witness",
]

# --regress gate: a fresh run may not be slower than the checked-in
# baseline by more than this factor on any gated latency metric
# (latency-noise headroom included; step counts are exact and need no
# tolerance, so latency is the regression signal).  Gated metrics:
#   dist         — every fixpoint_ms* leaf of BENCH_frontier_sharded.json
#   serve_async  — every p99_ms leaf of BENCH_serve_async.json OUTSIDE
#                  the `overload` block (2x offered load sheds by
#                  design; its tail is rejection-shaped, not a signal)
#   packed       — every fixpoint_ms* leaf of BENCH_frontier_packed.json
#                  (f32 and packed multi-query fixpoints at Q=8/64/256,
#                  plus the fixpoint_ms_tiles_* rows of the f32-vs-uint32
#                  tile-store sweep)
#   witness      — every fixpoint_ms* leaf of BENCH_witness.json (the
#                  witness level-carry overhead and the closure fast path)
REGRESS_FACTOR = 1.3
DIST_JSON = "BENCH_frontier_sharded.json"
SERVE_ASYNC_JSON = "BENCH_serve_async.json"
PACKED_JSON = "BENCH_frontier_packed.json"
WITNESS_JSON = "BENCH_witness.json"


def _collect_ms(
    d: dict, key_prefix: str = "fixpoint_ms", skip: str | None = None, prefix: str = ""
) -> dict[str, float]:
    """Flatten every ``<key_prefix>*`` leaf of a BENCH json (nested
    sections included) into dotted-path → milliseconds, skipping any
    subtree named ``skip``."""
    out: dict[str, float] = {}
    for k, v in d.items():
        if k == skip:
            continue
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_collect_ms(v, key_prefix, skip, path + "."))
        elif isinstance(k, str) and k.startswith(key_prefix) and isinstance(
            v, (int, float)
        ):
            out[path] = float(v)
    return out


def check_regressions(
    baseline: dict,
    fresh: dict,
    factor: float = REGRESS_FACTOR,
    key_prefix: str = "fixpoint_ms",
    skip: str | None = None,
):
    """Compare every gated latency metric of a fresh run against the
    checked-in baseline; returns (csv rows, regressed metric names)."""
    base_ms = _collect_ms(baseline, key_prefix, skip)
    new_ms = _collect_ms(fresh, key_prefix, skip)
    rows, failed = [], []
    for key, old in sorted(base_ms.items()):
        new = new_ms.get(key)
        if new is None:  # metric dropped from the schema: not a slowdown
            continue
        ratio = new / old if old > 0 else float("inf")
        ok = ratio <= factor
        rows.append(f"regress,{key},{old:.4f},{new:.4f},{ratio:.3f},{'ok' if ok else 'REGRESSED'}")
        if not ok:
            failed.append(key)
    return rows, failed


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "names", nargs="*",
        help=f"benchmarks to run (default: all of {KNOWN})",
    )
    ap.add_argument(
        "--regress", action="store_true",
        help=(
            "after the run, compare the gated subsets against their "
            f"checked-in baselines ({DIST_JSON} fixpoint-ms for `dist`, "
            f"{SERVE_ASYNC_JSON} p99-ms for `serve_async`, "
            f"{PACKED_JSON} fixpoint-ms for `packed`) and exit "
            f"non-zero on a > {REGRESS_FACTOR}x slowdown"
        ),
    )
    ap.add_argument(
        "--budget-bytes", type=int, default=None,
        help=(
            "tile-store byte budget for the `packed` subset's out-of-core "
            "run (default: a third of the full uint32 store at the "
            "400k-edge point)"
        ),
    )
    ap.add_argument(
        "--platform",
        help=(
            "free-form provenance note recorded in every BENCH_*.json env "
            "header (e.g. 'ci-cpu', 'v5p-8'); the header also records "
            "jax.default_backend() and the interpret-mode flag"
        ),
    )
    args = ap.parse_args()
    unknown = set(args.names) - set(KNOWN)
    if unknown:
        ap.error(f"unknown benchmark(s) {sorted(unknown)}; choose from {KNOWN}")
    selected = set(args.names) if args.names else set(KNOWN)

    # (name, baseline json, leaf-key prefix, skipped subtree)
    gates = [
        ("dist", DIST_JSON, "fixpoint_ms", None),
        ("serve_async", SERVE_ASYNC_JSON, "p99_ms", "overload"),
        ("packed", PACKED_JSON, "fixpoint_ms", None),
        ("witness", WITNESS_JSON, "fixpoint_ms", None),
    ]
    baselines: dict[str, dict] = {}
    if args.regress:
        gated = [g for g in gates if g[0] in selected]
        if not gated:
            ap.error(
                "--regress gates the `dist`, `serve_async`, `packed`, and "
                "`witness` subsets; include at least one in names"
            )
        for name, path, _, _ in gated:
            try:
                with open(path) as f:
                    baselines[name] = json.load(f)  # snapshot BEFORE the run overwrites it
            except FileNotFoundError:
                ap.error(f"--regress needs a checked-in {path} baseline")

    from repro import compile_cache

    from benchmarks import (
        common,
        fig2_costs,
        fig3_regions,
        fig4_estimation,
        frontier_level,
        frontier_sharded,
        plan_store,
        roofline,
        scenario6,
        serve_async,
        serve_throughput,
        table1_complexity,
        table2_queries,
        witness,
    )

    common.set_platform_note(args.platform)
    compile_cache.enable()

    modules = [
        ("table1", table1_complexity),
        ("table2", table2_queries),
        ("fig2", fig2_costs),
        ("fig3", fig3_regions),
        ("fig4", fig4_estimation),
        ("scenario6", scenario6),
        ("roofline", roofline),
        ("serve", serve_throughput),
        ("serve_async", serve_async),
        ("frontier", frontier_level),
        ("dist", frontier_sharded),
        ("plans", plan_store),
        ("packed", types.SimpleNamespace(
            run=lambda: roofline.run_packed(budget_bytes=args.budget_bytes)
        )),
        ("witness", witness),
    ]

    errored: list[str] = []
    for name, mod in modules:
        if name not in selected:
            continue
        t0 = time.time()
        print(f"# ==== {name} " + "=" * 50, flush=True)
        try:
            for row in mod.run():
                print(row)
        except Exception:  # noqa: BLE001 — finish the sweep, then fail it
            traceback.print_exc()
            print(f"{name},ERROR")
            errored.append(name)
        print(f"# {name} took {time.time() - t0:.1f}s", flush=True)

    if baselines:
        print("# ==== regress " + "=" * 50, flush=True)
        print("regress,metric,baseline_ms,fresh_ms,ratio,status")
        all_failed: list[str] = []
        for name, path, key_prefix, skip in gates:
            if name not in baselines:
                continue
            with open(path) as f:
                fresh = json.load(f)
            rows, failed = check_regressions(
                baselines[name], fresh, key_prefix=key_prefix, skip=skip
            )
            for row in rows:
                print(row)
            all_failed.extend(f"{name}:{m}" for m in failed)
        if all_failed:
            print(
                f"regress,FAIL,{len(all_failed)} metric(s) slower than "
                f"{REGRESS_FACTOR}x baseline: {';'.join(all_failed)}"
            )
            sys.exit(1)
        print(f"regress,OK,every gated latency metric within {REGRESS_FACTOR}x of baseline")
    if errored:
        print(f"# FAILED subsets: {','.join(errored)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
