"""Site-sharded fused frontier benchmark: the distributed fixpoint
(shape-bucketed per-site fused levels + ring frontier merge under
``shard_map``) vs the global single-grid fixpoint, at 1 / 2 / 4 / 8
sites.

Measures, on one random labeled graph and a wildcard-bearing automaton,
with a disjoint edge partition per site count:

* **fixpoint latency** — one batched ``s2_execute`` call through the
  ``frontier_kernel_sharded`` backend per site count vs the global
  ``frontier_kernel`` backend (same query batch, same tiles);
* **grid work** — each site's executed grid steps are its shape
  bucket's power-of-two class, not the worst site's schedule; the
  benchmark records the executed total AND ``pad_waste_ratio``
  (padded / useful steps), the cliff the bucketed refactor flattens;
* **meter fidelity** — per-site response meters summed across sites vs
  the instrumented host meter (exact on a disjoint partition).

Writes ``BENCH_frontier_sharded.json`` (stable schema) so the perf
trajectory accumulates across PRs.

Measurement caveat: this runs on a (1, 1) mesh, so the executor merges
every site's tiles into ONE deduplicated device grid (the distribution
model lives in the per-site meters and, on a real mesh, the ring
exchange) — the latency lane measures merged-expansion + per-site
metering overhead, and ``exec_grid_steps_total`` records the merged
grid it actually ran.  The ``grid_steps_*`` / ``pad_waste_ratio``
numbers are the *deployment* plan (each site on its own device,
``axis_size = n_sites``), exact on any backend; the multi-device ring
path itself is exercised by the 8-forced-host-device test in
``tests/test_frontier_sharded.py``.

Run:  PYTHONPATH=src python benchmarks/frontier_sharded.py
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import bench_env
from repro.core import paa, strategies
from repro.dist import compat
from repro.graph.generators import random_labeled_graph
from repro.graph.partition import Placement
from repro.kernels.frontier.ops import (
    build_level_plan,
    build_sharded_level_plan,
    make_blocked_graph,
    merge_staged_sites,
    stage_sharded_graph,
)

QUERY = "(l0|l1)* l2 .^-1"
SITE_COUNTS = (1, 2, 4, 8)


def _partition(g, n_sites: int, seed: int) -> Placement:
    rng = np.random.default_rng(seed)
    assign = rng.integers(0, n_sites, g.n_edges)
    site_edges = [np.nonzero(assign == s)[0].astype(np.int64) for s in range(n_sites)]
    return Placement(g, n_sites, site_edges, np.ones(g.n_edges, np.int32))


def _time_best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(
    n_nodes: int = 96,
    n_edges: int = 700,
    n_labels: int = 5,
    block: int = 32,
    repeats: int = 3,
    out: str = "BENCH_frontier_sharded.json",
    seed: int = 0,
) -> list[str]:
    g = random_labeled_graph(n_nodes, n_edges, n_labels, seed=seed)
    index = paa.HostIndex(g)
    ca = paa.compile_query(QUERY, g)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    starts = np.arange(0, n_nodes, n_nodes // 8, dtype=np.int32)[:8]

    global_plan = build_level_plan(ca, make_blocked_graph(g, block))
    result: dict = {
        "benchmark": "frontier_sharded",
        "env": bench_env(),
        "query": QUERY,
        "n_nodes": n_nodes,
        "n_edges": n_edges,
        "n_labels": n_labels,
        "block_size": block,
        "batch": len(starts),
        "grid_steps_global": int(np.asarray(global_plan.step).shape[0]),
        "sites": {},
    }

    # global fused backend (retrieval on the deduplicated union graph)
    placement1 = _partition(g, 1, seed)
    step_gl = strategies.make_s2_step_fn(
        ca, n_nodes, mesh, backend="frontier_kernel", graph=g, block_size=block
    )

    def run_backend(step_fn, placement):
        return strategies.s2_execute(
            mesh, placement, ca, starts, step_fn=step_fn,
        )

    acc_gl, _ = run_backend(step_gl, placement1)  # warm
    t_global = _time_best(lambda: run_backend(step_gl, placement1), repeats)
    result["fixpoint_ms_global"] = 1e3 * t_global

    host_uc = {int(s): strategies.s2_costs(ca, index, int(s)).unicast_symbols for s in starts}

    for n_sites in SITE_COUNTS:
        placement = _partition(g, n_sites, seed)
        site_graphs = [placement.local_graph(s) for s in range(n_sites)]
        # deployment plan: each site on its own device along the site axis
        plan = build_sharded_level_plan(ca, site_graphs, block, axis_size=n_sites)
        # executed plan on this (1, 1) mesh: all sites merged to one grid
        exec_plan = build_sharded_level_plan(
            ca, merge_staged_sites(stage_sharded_graph(site_graphs, block), 1), block
        )
        step_sh = strategies.make_s2_step_fn(
            ca, n_nodes, mesh, backend="frontier_kernel_sharded",
            placement=placement, block_size=block,
        )
        acc, costs = run_backend(step_sh, placement)  # warm + correctness
        assert (np.asarray(acc) == np.asarray(acc_gl)).all(), n_sites
        meter_exact = all(
            sum(c.site_unicast_symbols) == host_uc[int(s)]
            for c, s in zip(costs, starts)
        )
        t_sh = _time_best(lambda: run_backend(step_sh, placement), repeats)
        result["sites"][str(n_sites)] = {
            "fixpoint_ms_sharded": 1e3 * t_sh,
            "sharded_over_global": t_sh / t_global,
            # executed grid slots = each site's shape-bucket class
            "grid_steps_per_site": [
                next(b.n_steps for b in plan.buckets if s in b.sites)
                for s in range(n_sites)
            ],
            "grid_steps_total": plan.padded_steps,
            "exec_grid_steps_total": exec_plan.padded_steps,
            "useful_steps_total": plan.useful_steps,
            "pad_waste_ratio": plan.pad_waste_ratio,
            "bucket_shapes": [list(bs) for bs in plan.bucket_shapes],
            "per_site_meter_sums_to_host": bool(meter_exact),
        }

    with open(out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)

    rows = ["frontier_sharded,metric,value"]
    rows.append(f"frontier_sharded,fixpoint_ms_global,{result['fixpoint_ms_global']:.4f}")
    for n_sites in SITE_COUNTS:
        r = result["sites"][str(n_sites)]
        rows.append(
            f"frontier_sharded,fixpoint_ms_sharded_{n_sites}site,{r['fixpoint_ms_sharded']:.4f}"
        )
        rows.append(
            f"frontier_sharded,grid_steps_total_{n_sites}site,{r['grid_steps_total']}"
        )
        rows.append(
            f"frontier_sharded,pad_waste_ratio_{n_sites}site,{r['pad_waste_ratio']:.4f}"
        )
        rows.append(
            f"frontier_sharded,meter_exact_{n_sites}site,{int(r['per_site_meter_sums_to_host'])}"
        )
    rows.append(f"frontier_sharded,json,{out}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=96)
    ap.add_argument("--edges", type=int, default=700)
    ap.add_argument("--block", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_frontier_sharded.json")
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
