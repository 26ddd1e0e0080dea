"""Record the small profiler trace that ``test_tracing.py`` reads.

Runs, on the machine it is started on (a TPU for the recorded file), a
small twin (4,000 nodes) through the program's packed S2 executor under
the JAX profiler, with the harness's ``bench.window`` and ``bench.flush``
annotations and the profiler options of a traced run (no Python tracer,
annotation-level host events), and copies the ``.xplane.pb`` to
``--out``:

    python bench/tests/record_trace.py --out bench/tests/data/packed_small.xplane.pb
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax

    from repro.core import planner
    from repro.dist import compat
    from repro.graph.partition import Placement
    from repro.graph.structure import LabeledGraph
    from repro.serve import QueryService, ServeConfig
    from yardstick import placement, reference, tracing, twin

    g = twin.alibaba_like(n_nodes=4000, n_edges=20000, seed=0)
    lg = LabeledGraph(g.n_nodes, g.src, g.lbl, g.dst, g.labels)
    sites = placement.distribute(g.n_edges, 4, replication_rate=0.3, seed=1)
    placed = Placement(lg, sites.n_sites, sites.site_edges, sites.replication)
    params = planner.NetworkParams(n_peers=150, n_connections=450, replication_rate=0.2)
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    service = QueryService(placed, mesh, params, config=ServeConfig(
        n_rollouts=50, s2_backend="frontier_kernel_packed", s2_tile_dtype="uint32"))
    ref = reference.Evaluator(g.n_nodes, g.src, g.lbl, g.dst, g.labels)
    query = twin.TABLE2_QUERIES["q9"]
    starts = ref.valid_starts(query)
    service.submit(query, starts, strategy="S2")  # compile outside the trace

    with tempfile.TemporaryDirectory(dir=ROOT) as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench." + tracing.WINDOW_SPAN):
            for _ in range(2):
                service.enqueue(query, starts, strategy="S2")
                with jax.profiler.TraceAnnotation("bench.flush"):
                    service.flush()
        jax.profiler.stop_trace()
        path = tracing.find_xplane(d)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        shutil.copyfile(path, args.out)
    r = tracing.reduce(args.out)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes, window {r.window_s:.6f} s, "
          f"busy {r.busy_s:.6f} s, devices {r.n_devices}")
    for name, s in r.breakdown()["device_ops"]:
        print(f"  op {name}: {s:.6f} s")
    for name, s in r.breakdown()["idle_gaps"]:
        print(f"  idle under {name}: {s:.6f} s")


if __name__ == "__main__":
    main()
