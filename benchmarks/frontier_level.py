"""Fused frontier-level benchmark: one Pallas grid per BFS level vs the
per-transition dispatch baseline, and 1 vs 8 stacked queries through the
device-resident fixpoint.

Measures, on one random labeled graph and a wildcard-bearing automaton:

* **dispatch counts** per BFS level (jaxpr ``pallas_call`` equations) —
  the fused path is 1 by construction, the baseline pays one per
  (transition, label entry);
* **level latency** — ``expand_level_fused`` (one call) vs
  ``expand_level`` (per-transition calls + host-side merges);
* **multi-query throughput** — 8 queries stacked into the f32 row-tile
  minimum of ONE fixpoint vs 8 single-query fixpoints.

Writes ``BENCH_frontier.json`` (stable schema) so the perf trajectory
accumulates across PRs.

Measurement caveat: off-TPU this runs the Pallas interpreter, whose
per-grid-step cost scales with the full operand size (each output
revisit copies the whole (n_states·8, v_pad) buffer), so raw fused level
latency understates the TPU win; the per-query and stacked-fixpoint
numbers are the meaningful interpret-mode comparisons, and the dispatch
counts are exact on any backend.

Run:  PYTHONPATH=src python benchmarks/frontier_level.py
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.common import bench_env
from repro.core import paa
from repro.graph.generators import random_labeled_graph
from repro.kernels.frontier.frontier import count_pallas_calls, resolve_interpret
from repro.kernels.frontier.ops import (
    QPAD,
    build_level_plan,
    expand_level,
    expand_level_fused,
    make_blocked_graph,
    multi_query_reach,
    multi_source_reach,
    multi_source_reach_baseline,
    stack_start_masks,
)

QUERY = "(l0|l1)* l2 .^-1"  # union-star + wildcard-inverse: many grounded entries


def _time_best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(
    n_nodes: int = 192,
    n_edges: int = 1400,
    n_labels: int = 5,
    block: int = 64,
    repeats: int = 5,
    out: str = "BENCH_frontier.json",
    seed: int = 0,
    interpret: bool | None = None,
) -> list[str]:
    interpret = resolve_interpret(interpret)
    g = random_labeled_graph(n_nodes, n_edges, n_labels, seed=seed)
    bg = make_blocked_graph(g, block_size=block)
    ca = paa.compile_query(QUERY, g)
    plan = build_level_plan(ca, bg)

    rng = np.random.default_rng(seed)
    starts = rng.choice(n_nodes, size=QPAD, replace=False)
    masks = np.zeros((QPAD, n_nodes), np.float32)
    masks[np.arange(QPAD), starts] = 1.0
    f_stacked = jnp.asarray(stack_start_masks(plan, ca.start, masks))
    f_flat = jnp.asarray(np.asarray(f_stacked).reshape(ca.n_states, QPAD, -1)[:, 0, :])

    # ---- dispatches per level (jaxpr pallas_call count) -------------------
    disp_fused = count_pallas_calls(
        lambda x: expand_level_fused(plan, x, interpret=interpret), f_stacked
    )
    disp_base = count_pallas_calls(
        lambda x: expand_level(ca, bg, x, interpret=interpret), f_flat
    )

    # ---- level latency ----------------------------------------------------
    def level_fused():
        expand_level_fused(plan, f_stacked, interpret=interpret).block_until_ready()

    def level_base():
        expand_level(ca, bg, f_flat, interpret=interpret).block_until_ready()

    level_fused(), level_base()  # warm the jit caches
    t_fused = _time_best(level_fused, repeats)
    t_base = _time_best(level_base, repeats)

    # ---- fixpoint: per-transition host loop vs fused, 8×1 vs 1×8 ----------
    def fix_base():
        for i in range(QPAD):
            multi_source_reach_baseline(ca, bg, masks[i], interpret=interpret)

    def fix_q1():
        for i in range(QPAD):
            multi_source_reach(ca, bg, masks[i], interpret=interpret, plan=plan)

    def fix_q8():
        multi_query_reach(ca, bg, masks, interpret=interpret, plan=plan)

    fix_base(), fix_q1(), fix_q8()  # warm (shared fixpoint trace)
    t_qb = _time_best(fix_base, repeats)
    t_q1 = _time_best(fix_q1, repeats)
    t_q8 = _time_best(fix_q8, repeats)

    result = {
        "benchmark": "frontier_level",
        "env": bench_env(),
        "query": QUERY,
        "n_nodes": n_nodes,
        "n_edges": n_edges,
        "n_labels": n_labels,
        "block_size": block,
        "n_transitions": len(ca.transitions),
        "grid_steps_fused": int(np.asarray(plan.step).shape[0]),
        "dispatches_per_level_fused": disp_fused,
        "dispatches_per_level_baseline": disp_base,
        # the fused level carries QPAD stacked queries per call, the
        # baseline one — per-query is the comparable unit
        "level_ms_fused": 1e3 * t_fused,
        "level_ms_baseline": 1e3 * t_base,
        "level_speedup_per_query": t_base / (t_fused / QPAD),
        "fixpoint_ms_baseline_8x1": 1e3 * t_qb,
        "fixpoint_ms_fused_8x1": 1e3 * t_q1,
        "fixpoint_ms_fused_1x8_stacked": 1e3 * t_q8,
        "multi_query_speedup": t_q1 / t_q8,
        "fused_speedup_vs_baseline": t_qb / t_q8,
        "interpret": interpret,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)

    rows = ["frontier,metric,value"]
    for k in (
        "dispatches_per_level_fused", "dispatches_per_level_baseline",
        "level_ms_fused", "level_ms_baseline", "level_speedup_per_query",
        "fixpoint_ms_baseline_8x1", "fixpoint_ms_fused_8x1",
        "fixpoint_ms_fused_1x8_stacked", "multi_query_speedup",
        "fused_speedup_vs_baseline",
    ):
        rows.append(f"frontier,{k},{result[k]:.4f}")
    rows.append(f"frontier,json,{out}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=192)
    ap.add_argument("--edges", type=int, default=1400)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default="BENCH_frontier.json")
    args = ap.parse_args()
    print(
        "\n".join(
            run(
                n_nodes=args.nodes, n_edges=args.edges, block=args.block,
                repeats=args.repeats, out=args.out,
            )
        )
    )


if __name__ == "__main__":
    main()
