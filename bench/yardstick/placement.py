"""Edge placement over sites and the peers' overlay, as the benchmark
builds them.

Copies of the program's ``repro.graph.partition.distribute`` and
``random_overlay`` that return plain arrays (the benchmark wraps them in
the program's ``Placement`` and ``OverlayNetwork`` types only to hand
them to the system under test).  ``bench/tests/test_copies.py`` checks
that both give the program's placement and overlay byte for byte.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Sites:
    """Per-site edge ids (sorted) and, per edge, how many sites hold it."""

    n_sites: int
    site_edges: list[np.ndarray]
    replication: np.ndarray


def distribute(
    n_edges: int,
    n_sites: int,
    replication_rate: float = 0.2,
    skew: float = 0.0,
    seed: int = 0,
) -> Sites:
    """Place each edge on sites independently with probability
    ``replication_rate`` (per-site Bernoulli, so E[copies] = k·N_p = K),
    then assign orphan edges one uniform site (every resource exists
    somewhere).  ``skew`` > 0 biases site popularity (Dirichlet) to model
    autonomous peers hosting very different amounts of data — 'arbitrarily
    distributed' includes non-uniform placements."""
    rng = np.random.default_rng(seed)
    E = n_edges
    if skew > 0:
        site_w = rng.dirichlet(np.full(n_sites, 1.0 / (skew + 1e-9)))
        site_p = np.clip(site_w * replication_rate * n_sites, 0.0, 1.0)
    else:
        site_p = np.full(n_sites, replication_rate)

    holds = rng.random((n_sites, E)) < site_p[:, None]
    orphan = ~holds.any(axis=0)
    if orphan.any():
        owners = rng.integers(0, n_sites, orphan.sum())
        holds[owners, np.nonzero(orphan)[0]] = True

    site_edges = [np.nonzero(holds[s])[0].astype(np.int64) for s in range(n_sites)]
    replication = holds.sum(axis=0).astype(np.int32)
    return Sites(n_sites, site_edges, replication)


def random_overlay(n_peers: int, mean_degree: float, seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """(adj_src, adj_dst) of a connected random overlay: a ring for
    connectivity plus random chords up to the mean degree d = N_c/N_p,
    each undirected edge stored both ways."""
    rng = np.random.default_rng(seed)
    ring = [(i, (i + 1) % n_peers) for i in range(n_peers)]
    target_nc = int(round(mean_degree * n_peers))
    chords: set[tuple[int, int]] = set()
    existing = {tuple(sorted(e)) for e in ring}
    while len(chords) + len(ring) < target_nc:
        a, b = rng.integers(0, n_peers, 2)
        if a == b:
            continue
        key = tuple(sorted((int(a), int(b))))
        if key in existing or key in chords:
            continue
        chords.add(key)
    edges = ring + sorted(chords)
    src = np.array([e[0] for e in edges] + [e[1] for e in edges], np.int32)
    dst = np.array([e[1] for e in edges] + [e[0] for e in edges], np.int32)
    return src, dst
