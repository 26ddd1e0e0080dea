"""Shared set-up of the benchmark's own tests (run by explicit path:
``python -m pytest bench/tests``; the repository's tier-1 suite collects
``tests/`` only).  They run on the CPU, at small sizes."""

from __future__ import annotations

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]


def tiny_cell(name: str):
    """A cell of BENCHMARK.json cut to a size the CPU's Pallas interpreter
    runs in seconds: a 4,000-node twin, 20 peers, 50 rollouts, three
    Table-2 queries and three callers, or 3 requests/s open loop."""
    from yardstick import harness

    cell = harness.load_cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.mix = copy.deepcopy(cell.mix)
    cell.config["graph"].update(n_nodes=4000, n_edges=20000)
    cell.config["network"]["n_peers"] = 20
    cell.config["serve"]["n_rollouts"] = 50
    cell.config["placement"]["n_sites"] = min(cell.config["placement"]["n_sites"], 20)
    if cell.mix["queries"]["source"] == "table2":
        cell.mix["queries"]["names"] = ["q1", "q6", "q11"]
        cell.mix["clients"] = 3
    else:
        cell.mix["rate_qps"] = 3.0
    return cell
