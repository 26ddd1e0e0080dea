"""The plain reference agrees with the program's PAA oracle on a small
twin, for the Table-2 queries and a seed-path stream, and finds the
same valid start nodes.  (It is the program that is under test; this
only shows that the reference reads the query syntax the same way.)"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import paa
from repro.graph.structure import LabeledGraph, to_device_graph
from yardstick import reference, twin, workloads


@pytest.fixture(scope="module")
def small():
    g = twin.alibaba_like(n_nodes=4000, n_edges=20000, seed=3)
    lg = LabeledGraph(g.n_nodes, g.src, g.lbl, g.dst, g.labels)
    return g, lg, to_device_graph(lg), reference.Evaluator(g.n_nodes, g.src, g.lbl, g.dst, g.labels)


def _oracle(query, lg, dg, starts):
    ca = paa.compile_query(query, lg)
    return [np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0] for s in starts]


@pytest.mark.parametrize("name", ["q1", "q2", "q6", "q9", "q10", "q11", "q12"])
def test_table2_matches_oracle(small, name):
    g, lg, dg, ref = small
    query = twin.TABLE2_QUERIES[name]
    starts = ref.valid_starts(query)
    assert np.array_equal(starts, paa.valid_start_nodes(paa.compile_query(query, lg), lg))
    starts = starts[:: max(1, len(starts) // 6)]
    for a, b in zip(ref.answers(query, starts), _oracle(query, lg, dg, starts)):
        assert np.array_equal(a, b)


def test_seed_path_and_syntax_match_oracle(small):
    g, lg, dg, ref = small
    stream = workloads.generate(g, workloads.WorkloadConfig(n_queries=30, seed=9))
    queries = {w.query: w.starts for w in stream}
    queries.update({
        "(cooc_0)* .": np.array([5, 17], np.int32),
        "cooc_1? (cooc_2|cooc_3)+": np.array([1, 2, 3], np.int32),
        "cooc_0^-1 cooc_0": np.array([0, 9], np.int32),
        "{interaction, binding}+": np.array([0, 1, 2], np.int32),
    })
    for query, starts in queries.items():
        for a, b in zip(ref.answers(query, starts), _oracle(query, lg, dg, starts)):
            assert np.array_equal(a, b), query
