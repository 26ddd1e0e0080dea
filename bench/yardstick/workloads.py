"""The seed-path query stream, as the benchmark draws it.

A copy of the program's ``repro.graph.workloads`` (seed-path
instantiation with hot/cold skew), kept with the benchmark so that the
traffic cannot move when a later change edits the program's generator.
``bench/tests/test_copies.py`` checks that both draw the same stream.

Random-walk a real path through the graph, then generalize its label
sequence into a query (wildcards, unions, closures), so every query is
answerable from its first start node.  A pool of ``hot_pool`` classes
takes ``hot_fraction`` of the stream, rank-weighted; the rest are fresh.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from yardstick.twin import Graph


@dataclasses.dataclass(frozen=True)
class WorkloadConfig:
    n_queries: int = 100
    min_len: int = 2
    max_len: int = 4
    wildcard_prob: float = 0.10
    union_prob: float = 0.20
    closure_prob: float = 0.15
    hot_fraction: float = 0.8
    hot_pool: int = 8
    min_starts: int = 1
    max_starts: int = 8
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class WorkloadQuery:
    query: str
    starts: np.ndarray  # (k,) int32; starts[0] is the seed-path witness
    hot: bool


def _out_csr(graph: Graph) -> tuple[np.ndarray, np.ndarray]:
    """Edge ids grouped by source node: (order, offsets)."""
    order = np.argsort(graph.src, kind="stable")
    offsets = np.zeros(graph.n_nodes + 1, np.int64)
    np.add.at(offsets[1:], graph.src, 1)
    np.cumsum(offsets, out=offsets)
    return order, offsets


def _seed_path(
    graph: Graph,
    order: np.ndarray,
    offsets: np.ndarray,
    length: int,
    rng: np.random.Generator,
) -> tuple[int, list[int]]:
    """Random-walk ``length`` edges; returns (source node, label ids).

    Starts are drawn from nodes with outgoing edges; a dead end cuts
    the walk short (the prefix is still a witnessed path)."""
    sources = np.unique(graph.src)
    if len(sources) == 0:
        return 0, []
    start = int(sources[rng.integers(len(sources))])
    node, labels = start, []
    for _ in range(length):
        lo, hi = offsets[node], offsets[node + 1]
        if hi <= lo:
            break
        eid = int(order[rng.integers(lo, hi)])
        labels.append(int(graph.lbl[eid]))
        node = int(graph.dst[eid])
    return start, labels


def _instantiate(
    graph: Graph, labels: list[int], cfg: WorkloadConfig, rng: np.random.Generator
) -> str:
    """Generalize a witnessed label sequence into a query string."""
    atoms = []
    for lid in labels:
        r = rng.random()
        if r < cfg.wildcard_prob:
            atom = "."
        elif r < cfg.wildcard_prob + cfg.union_prob and graph.n_labels > 1:
            other = int(rng.integers(graph.n_labels - 1))
            other += other >= lid  # any label but the witnessed one
            atom = f"({graph.labels[lid]}|{graph.labels[other]})"
        else:
            atom = graph.labels[lid]
        if rng.random() < cfg.closure_prob:
            # '+' keeps the witness valid unconditionally; '*' widens
            # (and on a wildcard atom forces the S2-flavored all-pairs
            # shape the closure knob exists to produce)
            atom = f"({atom})" + ("*" if rng.random() < 0.5 else "+")
        atoms.append(atom)
    return " ".join(atoms)


def generate(graph: Graph, config: WorkloadConfig | None = None) -> list[WorkloadQuery]:
    """The deterministic request stream for ``config.seed``.

    Every query is answerable from its first start node by construction
    (the seed path's source witnesses the un-generalized sequence, and
    every generalization step only widens the language)."""
    cfg = config or WorkloadConfig()
    rng = np.random.default_rng(cfg.seed)
    order, offsets = _out_csr(graph)

    def fresh() -> tuple[str, int]:
        length = int(rng.integers(cfg.min_len, cfg.max_len + 1))
        source, labels = _seed_path(graph, order, offsets, length, rng)
        while not labels:  # isolated pocket: rewalk
            source, labels = _seed_path(graph, order, offsets, length, rng)
        return _instantiate(graph, labels, cfg, rng), source

    hot_classes = [fresh() for _ in range(cfg.hot_pool)]
    hot_w = 1.0 / (1.0 + np.arange(len(hot_classes)))
    hot_w /= hot_w.sum()

    out: list[WorkloadQuery] = []
    for _ in range(cfg.n_queries):
        hot = rng.random() < cfg.hot_fraction and hot_classes
        if hot:
            query, source = hot_classes[int(rng.choice(len(hot_classes), p=hot_w))]
        else:
            query, source = fresh()
        k = int(rng.integers(cfg.min_starts, cfg.max_starts + 1))
        extras = rng.integers(0, graph.n_nodes, max(k - 1, 0))
        starts = np.concatenate([[source], extras]).astype(np.int32)
        out.append(WorkloadQuery(query=query, starts=starts, hot=bool(hot)))
    return out
