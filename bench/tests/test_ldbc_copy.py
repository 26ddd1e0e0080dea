"""The benchmark's copy of the LDBC SNB generator gives the program's
graph byte for byte at a small scale; both cells this deployment brought
load; and every query of ``paths_closed`` has at most 128 valid starts
(one reference chunk) at the configuration's scale factor."""

from __future__ import annotations

import pytest

from repro.graph import generators
from yardstick import harness
from yardstick.reference import Evaluator

CELLS = ["twin4site.hot_open", "ldbc_snb.paths_closed"]


@pytest.mark.parametrize("kw,dictionary_share", [
    (dict(scale_factor=0.0003, seed=3), 0.005),
    (dict(scale_factor=0.002, seed=0), 1.0),
])
def test_ldbc_copy_matches_program(monkeypatch, kw, dictionary_share):
    copy = harness.load_generator("ldbc_snb")
    # both modules' fixed dictionaries cut alike, for a graph of a few hundred nodes
    small = {k: max(round(n * dictionary_share), 3) for k, n in generators.LDBC_STATIC.items()}
    monkeypatch.setattr(copy, "LDBC_STATIC", small)
    monkeypatch.setattr(generators, "LDBC_STATIC", small)
    mine = copy.generate(**kw)
    theirs = generators.ldbc_snb_like(**kw)
    assert mine.n_nodes == theirs.n_nodes
    assert mine.labels == theirs.labels
    for k in ("src", "lbl", "dst"):
        a, b = getattr(mine, k), getattr(theirs, k)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("name", CELLS)
def test_new_cell_loads(name):
    cell = harness.load_cell(name)
    assert cell.chips == 1 and cell.config["control"]["serve"]
    assert callable(harness.load_generator(cell.config["graph"]["generator"]).generate)
    readers = [harness.load_reader(m["name"]) for m in cell.end_to_end + cell.per_layer]
    assert readers and all(callable(r.read) for r in readers)


def test_paths_closed_valid_starts_fit_one_chunk():
    cell = harness.load_cell("ldbc_snb.paths_closed")
    params = dict(cell.config["graph"])
    g = harness.load_generator(params.pop("generator")).generate(seed=cell.config["seed"], **params)
    ref = Evaluator(g.n_nodes, g.src, g.lbl, g.dst, g.labels)
    counts = [len(ref.valid_starts(q)) for q in cell.mix["queries"]["queries"]]
    assert len(counts) == 8 and all(0 < n <= 128 for n in counts), counts
