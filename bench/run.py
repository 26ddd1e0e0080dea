"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
deployment (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<mix>.json``) are found by name.  The run builds the
deployment from ``--seed``, warms every shape the traffic uses (counted
as ``setup_s``), measures ``--seconds`` of traffic through the program's
``AsyncQueryService``, drains, and compares every answered request with
the plain reference in ``bench/yardstick/reference.py``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler, with the program
calls that the readers declare timed, and the metrics are the cell's
per-layer metrics.  Each metric is read by ``bench/metrics/<name>.py``,
or by the reader of the name before its last dot.
The last line of standard output is the result object; the numbers
compared and their limits are the last lines of standard error.

``--control 1`` runs the configuration's control (its ``control``
overrides: the program with a guarantee broken), which has to come out
not correct.  It refuses to run, and exits with code 2, where JAX's
first device is not a TPU or there are fewer chips than the cell asks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from yardstick import harness

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                             control=bool(args.control))
    except harness.NoChip as e:
        print(f"bench/run.py: {e}; not running", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
