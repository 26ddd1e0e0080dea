"""End-to-end driver (the paper's kind: distributed query serving).

1. build the Alibaba statistical twin and distribute it arbitrarily over
   sites with replication (the paper's non-localized setting),
2. probe the network (§5.2.1) and stand up a ``repro.serve.QueryService``
   over the placement — plan caching, signature-batched execution, and
   cost-feedback recalibration included,
3. replay a Table-2 query mix through the service twice (cold, then with
   a warm plan cache) and verify every answer against the centralized
   PAA oracle.

Run:  PYTHONPATH=src python examples/plan_and_serve_rpq.py [--small]
"""

import argparse

import numpy as np

import jax

from repro import compile_cache
from repro.core import paa, planner
from repro.dist import compat
from repro.graph import generators
from repro.graph.partition import distribute, random_overlay
from repro.graph.structure import to_device_graph
from repro.serve import QueryService, ServeConfig


def make_serving_mesh(n_exec_sites: int):
    """Size the mesh from the actual device count (the seed hardcoded
    (1, 1), so multi-device runs never exercised the site axis): the
    site axis gets the largest factor of ``n_exec_sites`` that divides
    the device count, the rest of the devices batch queries on
    ``model`` — every device is used."""
    import math

    n = jax.device_count()
    data = math.gcd(n, n_exec_sites)
    return compat.make_mesh((data, n // data), ("data", "model"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true", help="40k-edge twin (fast)")
    ap.add_argument("--queries", default="q1,q2,q6,q11")
    args = ap.parse_args()
    compile_cache.enable()

    if args.small:
        g = generators.alibaba_like(n_nodes=8000, n_edges=40000, seed=0)
    else:
        g = generators.alibaba_like()
    print(f"twin: {g.n_nodes} nodes {g.n_edges} edges")

    net = random_overlay(150, 3.0, seed=1)
    probe_placement = distribute(g, 150, replication_rate=0.2, seed=1)
    params = planner.probe_network(net, probe_placement)
    print(f"probed: N_p={params.n_peers} N_c={params.n_connections} k̂={params.replication_rate:.3f}")

    n_exec_sites = 4
    mesh = make_serving_mesh(n_exec_sites)
    print(f"mesh: {dict(mesh.shape)} over {jax.device_count()} device(s)")
    exec_placement = distribute(g, n_exec_sites, replication_rate=0.3, seed=2)
    dg = to_device_graph(g)

    service = QueryService(
        exec_placement, mesh, params,
        config=ServeConfig(n_rollouts=600, seed=3),
    )

    names = args.queries.split(",")
    for replay in ("cold", "warm"):
        tickets = []
        for qname in names:
            query = generators.TABLE2_QUERIES[qname]
            ca = paa.compile_query(query, g)
            starts = paa.valid_start_nodes(ca, g)[:2]
            if len(starts) == 0:
                print(f"{qname}: no valid start nodes, skipped")
                continue
            tickets.append((qname, ca, service.enqueue(query, starts)))
        service.flush()  # one batching window: plans, batches, executes

        print(f"\n--- {replay} replay ---")
        for qname, ca, t in tickets:
            ans = t.result()
            plan = ans.plan
            print(
                f"{qname}: {ans.strategy} ({plan.choice.reason}) "
                f"discr={plan.choice.discr:.4f} k/d={plan.choice.k_over_d:.4f} "
                f"cap={plan.s2_cost_cap} cache_hit={ans.plan_cache_hit} "
                f"latency={ans.latency_s * 1e3:.1f}ms"
            )
            for i, s in enumerate(ans.starts):
                oracle = set(
                    np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist()
                )
                status = "OK" if ans.answers[i] == oracle else "MISMATCH"
                print(f"  start {int(s)}: {len(ans.answers[i])} answers [{status}]")

    s = service.summary()
    print(
        f"\nservice: {s['n_queries']} queries, {s['queries_per_sec']:.2f} q/s, "
        f"p50={s['p50_latency_s'] * 1e3:.1f}ms p95={s['p95_latency_s'] * 1e3:.1f}ms, "
        f"plan-cache hit rate {s['plan_cache_hit_rate']:.2f}, "
        f"exec cache builds {s['exec_cache']['builds']}"
    )


if __name__ == "__main__":
    main()
