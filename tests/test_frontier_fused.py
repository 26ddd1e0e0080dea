"""Fused frontier-level kernel: oracle equivalence (wildcards, inverses,
empty label stores, stacked queries), the one-dispatch-per-level
acceptance criterion, and the device-resident fixpoint."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import paa
from repro.graph.generators import random_labeled_graph
from repro.graph.structure import LabeledGraph, example_graph, to_device_graph
from repro.kernels.frontier.frontier import count_pallas_calls, unpack_schedule
from repro.kernels.frontier.ops import (
    QPAD,
    build_level_plan,
    expand_level,
    expand_level_fused,
    make_blocked_graph,
    multi_query_reach,
    multi_source_reach,
    multi_source_reach_baseline,
    reach_fixpoint,
    stack_start_masks,
)
from repro.kernels.frontier.ref import fused_level_ref


def _sparse_label_graph():
    """A graph whose vocabulary has a label with zero edges (l2), so
    wildcard expansion and direct references both hit an empty store."""
    rng = np.random.default_rng(5)
    n_nodes, n_edges = 45, 200
    src = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    dst = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    lbl = rng.choice([0, 1, 3], n_edges).astype(np.int32)  # label 2 never occurs
    return LabeledGraph(n_nodes, src, lbl, dst, ["l0", "l1", "l2", "l3"])


SWEEP = [
    # (graph factory, block size, queries)
    (lambda: example_graph(), 8, ["a* b b", "a c (a|b)", "(a|b)+", "a* b^-1"]),
    (
        lambda: random_labeled_graph(50, 220, 3, seed=7),
        16,
        ["l0 (l1|l2)* l0", ". l1", "l0* .^-1", "(l0|l2)+ l1?"],
    ),
    (
        _sparse_label_graph,
        8,
        ["l0 l2 l1", "l2* l0", "(l0|l2)+", ". l3^-1", "l0 .* l3"],
    ),
]


@pytest.mark.parametrize("case", range(len(SWEEP)))
def test_fused_level_matches_dense_oracle(case):
    """One fused level == the dense per-transition oracle on random
    multi-query frontiers (all 8 stacked rows exercised)."""
    factory, block, queries = SWEEP[case]
    g = factory()
    bg = make_blocked_graph(g, block_size=block)
    rng = np.random.default_rng(case)
    for expr in queries:
        ca = paa.compile_query(expr, g)
        plan = build_level_plan(ca, bg)
        f3 = (rng.random((ca.n_states, QPAD, bg.v_pad)) < 0.3).astype(np.float32)
        f3[:, :, g.n_nodes :] = 0.0  # padded node columns stay empty
        got = np.asarray(
            expand_level_fused(plan, jnp.asarray(f3.reshape(-1, bg.v_pad)), interpret=True)
        ).reshape(ca.n_states, QPAD, bg.v_pad)
        want = fused_level_ref(ca, g, f3)
        assert (got == want).all(), expr


@pytest.mark.parametrize("case", range(len(SWEEP)))
@pytest.mark.parametrize("n_queries", [1, 3, 8])
def test_multi_query_reach_bit_exact_per_query(case, n_queries):
    """Q stacked queries' visited sets are bit-exact vs the single-source
    PAA oracle — stacking must not leak between row lanes."""
    factory, block, queries = SWEEP[case]
    g = factory()
    dg = to_device_graph(g)
    bg = make_blocked_graph(g, block_size=block)
    rng = np.random.default_rng(100 * case + n_queries)
    for expr in queries[:2]:
        ca = paa.compile_query(expr, g)
        plan = build_level_plan(ca, bg)
        starts = rng.choice(g.n_nodes, size=n_queries, replace=False)
        masks = np.zeros((n_queries, g.n_nodes), np.float32)
        masks[np.arange(n_queries), starts] = 1.0
        got = multi_query_reach(ca, bg, masks, interpret=True, plan=plan)
        for i, s in enumerate(starts):
            want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
            assert (got[i] == want).all(), (expr, int(s))


def test_multi_query_reach_chunks_past_qpad():
    """More than q_pad queries split into multiple fixpoint chunks."""
    g = example_graph()
    dg = to_device_graph(g)
    bg = make_blocked_graph(g, block_size=8)
    ca = paa.compile_query("(a|b)+", g)
    n_q = QPAD + 3
    starts = np.arange(n_q) % g.n_nodes
    masks = np.zeros((n_q, g.n_nodes), np.float32)
    masks[np.arange(n_q), starts] = 1.0
    got = multi_query_reach(ca, bg, masks, interpret=True)
    for i, s in enumerate(starts):
        want = np.asarray(paa.answers_single_source(ca, dg, int(s)))
        assert (got[i] == want).all(), int(s)


def test_fused_matches_per_transition_baseline_fixpoint():
    """The fused fixpoint and the host-loop per-transition baseline agree
    (they share nothing but the packed tiles)."""
    g = random_labeled_graph(40, 170, 4, seed=3)
    bg = make_blocked_graph(g, block_size=8)
    ca = paa.compile_query("(l0|l1)* l2 .^-1", g)
    plan = build_level_plan(ca, bg)
    for start in range(0, g.n_nodes, 11):
        mask = np.zeros(g.n_nodes, np.float32)
        mask[start] = 1.0
        fused = multi_source_reach(ca, bg, mask, interpret=True, plan=plan)
        base = multi_source_reach_baseline(ca, bg, mask, interpret=True)
        assert (fused == base).all(), start


def test_one_pallas_call_per_level_regardless_of_transitions():
    """Acceptance criterion: the fused level is ONE pallas_call however
    many transitions × labels the automaton grounds to (wildcard + inverse
    included), while the baseline pays one per (transition, label entry)."""
    g = random_labeled_graph(40, 180, 4, seed=1)
    bg = make_blocked_graph(g, block_size=8)
    for expr in ["(l0|l1)* l2 .^-1", ". .", "l0 l1 l2 l3"]:
        ca = paa.compile_query(expr, g)
        plan = build_level_plan(ca, bg)
        f = jnp.asarray(
            stack_start_masks(plan, ca.start, np.ones((1, g.n_nodes), np.float32))
        )
        n_fused = count_pallas_calls(
            lambda x: expand_level_fused(plan, x, interpret=True), f
        )
        n_base = count_pallas_calls(
            lambda x: expand_level(ca, bg, x, interpret=True), f[: ca.n_states]
        )
        assert n_fused == 1, expr
        assert n_base >= len(ca.transitions), expr  # wildcards only add more


def test_fixpoint_is_device_resident():
    """The whole BFS fixpoint traces to a single pallas_call inside one
    while_loop — no host round-trips between levels (the baseline's
    per-level np.asarray sync is gone)."""
    g = random_labeled_graph(40, 180, 4, seed=1)
    bg = make_blocked_graph(g, block_size=8)
    ca = paa.compile_query("(l0|l1)* l2 .^-1", g)
    plan = build_level_plan(ca, bg)
    f = jnp.asarray(
        stack_start_masks(plan, ca.start, np.ones((1, g.n_nodes), np.float32))
    )
    assert (
        count_pallas_calls(
            lambda x: reach_fixpoint(plan, x, max_levels=64, interpret=True), f
        )
        == 1
    )


def test_plan_covers_every_output_block():
    """Every (dst_state, block_col) output block gets at least one grid
    step (real or zero-tile cover), and each block's first step is marked
    exactly once — the kernel's zero-init contract."""
    g = _sparse_label_graph()
    bg = make_blocked_graph(g, block_size=8)
    ca = paa.compile_query("l0 l2* (l1|l3)^-1", g)
    plan = build_level_plan(ca, bg)
    nb = plan.v_pad // plan.block_size
    c = unpack_schedule(plan.step, plan.src, plan.dst)
    orows, ocols, firsts = c["o_rows"], c["o_cols"], c["firsts"]
    blocks = set(zip(orows.tolist(), ocols.tolist()))
    assert blocks == {(s, c) for s in range(ca.n_states) for c in range(nb)}
    # sorted by (o_row, o_col); firsts flags each block's first step only
    key = orows.astype(np.int64) * nb + ocols
    assert (np.diff(key) >= 0).all()
    assert firsts.sum() == ca.n_states * nb
    assert (firsts[np.r_[True, np.diff(key) > 0]] == 1).all()
