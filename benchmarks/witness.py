"""Witness-semantics benchmark: the level-carry overhead and the
query-class fast paths (PR 9).

Two questions, one random labeled graph:

* **What does a witness cost?**  The level-carrying fixpoints
  (``reach_fixpoint_levels`` / ``reach_fixpoint_packed_levels``) vs
  their pairs-only twins on the same fused Stage-B schedule — the carry
  is one extra f32 plane (packed: one per *lane*, 32× the packed word
  bytes) plus a ``where`` per level, so the overhead should be a small
  constant factor, not a blow-up.

* **What does the classifier buy?**  A pure-closure query (``a*``)
  through the *general* compiled automaton vs the planner's reduced
  1-state form (:func:`repro.core.planner.reduce_automaton`): half the
  frontier rows, half the fused grid.  The acceptance gate for PR 9 is
  bit-exact answers and ≥ 1.5× on the fast path (interpret mode).

Writes ``BENCH_witness.json``; every latency leaf is ``fixpoint_ms*``-
prefixed so the ``witness`` subset rides the stock ``--regress`` gate.

Measurement caveat: off-TPU the Pallas interpreter's per-grid-step cost
scales with operand size, so absolute times overstate TPU cost; the
*ratios* (witness overhead, fast-path speedup) are the meaningful
interpret-mode numbers.

Run:  PYTHONPATH=src python -m benchmarks.run witness
"""

from __future__ import annotations

import json
import time

import numpy as np

import jax.numpy as jnp

from benchmarks.common import bench_env
from repro.core import paa, planner
from repro.kernels.frontier.frontier import resolve_interpret
from repro.kernels.frontier.ops import (
    QPAD,
    build_level_plan,
    make_blocked_graph,
    reach_fixpoint,
    reach_fixpoint_levels,
    reach_fixpoint_packed,
    reach_fixpoint_packed_levels,
    stack_start_masks,
    stack_start_masks_packed,
)
from repro.graph.generators import random_labeled_graph

CLOSURE_QUERY = "a*"
GENERAL_QUERY = "(a|b)* c"


def _time_best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _answers(visited: np.ndarray, n_states: int, q_pad: int, accepting) -> np.ndarray:
    """Accepting-row union of a flat (n_states·q_pad, v_pad) visited plane."""
    v3 = np.asarray(visited).reshape(n_states, q_pad, -1)
    return v3[list(accepting)].max(axis=0) > 0


def run(
    n_nodes: int = 256,
    n_edges: int = 2400,
    n_labels: int = 3,
    block: int = 64,
    repeats: int = 5,
    out: str = "BENCH_witness.json",
    seed: int = 0,
    interpret: bool | None = None,
) -> list[str]:
    interpret = resolve_interpret(interpret)
    g = random_labeled_graph(n_nodes, n_edges, n_labels, seed=seed)
    bg = make_blocked_graph(g, block_size=block)
    rng = np.random.default_rng(seed)
    starts = rng.choice(n_nodes, size=QPAD, replace=False)
    masks = np.zeros((QPAD, n_nodes), np.float32)
    masks[np.arange(QPAD), starts] = 1.0

    # ---- witness-carry overhead on a general automaton --------------------
    ca = paa.compile_query(GENERAL_QUERY, g)
    plan = build_level_plan(ca, bg)
    f0 = jnp.asarray(stack_start_masks(plan, ca.start, masks))
    f0p = jnp.asarray(stack_start_masks_packed(plan, ca.start, masks))

    def pairs_f32():
        reach_fixpoint(plan, f0, interpret=interpret).block_until_ready()

    def witness_f32():
        reach_fixpoint_levels(plan, f0, interpret=interpret)[1].block_until_ready()

    def pairs_packed():
        reach_fixpoint_packed(plan, f0p, interpret=interpret).block_until_ready()

    def witness_packed():
        reach_fixpoint_packed_levels(plan, f0p, interpret=interpret)[1].block_until_ready()

    pairs_f32(), witness_f32(), pairs_packed(), witness_packed()  # warm jit
    t_pairs_f32 = _time_best(pairs_f32, repeats)
    t_wit_f32 = _time_best(witness_f32, repeats)
    t_pairs_packed = _time_best(pairs_packed, repeats)
    t_wit_packed = _time_best(witness_packed, repeats)

    # ---- closure fast path: reduced 1-state automaton vs general PAA ------
    ca_gen = paa.compile_query(CLOSURE_QUERY, g)
    qc = planner.classify_query(CLOSURE_QUERY)
    ca_fast = planner.reduce_automaton(ca_gen, qc)
    assert ca_fast.n_states == 1 and ca_gen.n_states > 1
    plan_gen = build_level_plan(ca_gen, bg)
    plan_fast = build_level_plan(ca_fast, bg)
    fg = jnp.asarray(stack_start_masks(plan_gen, ca_gen.start, masks))
    ff = jnp.asarray(stack_start_masks(plan_fast, ca_fast.start, masks))

    def closure_general():
        return reach_fixpoint(plan_gen, fg, interpret=interpret).block_until_ready()

    def closure_fast():
        return reach_fixpoint(plan_fast, ff, interpret=interpret).block_until_ready()

    v_gen, v_fast = closure_general(), closure_fast()  # warm + correctness
    a_gen = _answers(v_gen, ca_gen.n_states, plan_gen.q_pad, ca_gen.accepting)
    a_fast = _answers(v_fast, 1, plan_fast.q_pad, (0,))
    bit_exact = bool((a_gen[:, :n_nodes] == a_fast[:, :n_nodes]).all())
    t_gen = _time_best(closure_general, repeats)
    t_fast = _time_best(closure_fast, repeats)

    result = {
        "benchmark": "witness",
        "env": bench_env(),
        "n_nodes": n_nodes,
        "n_edges": n_edges,
        "n_labels": n_labels,
        "block_size": block,
        "n_queries": QPAD,
        "witness_overhead": {
            "query": GENERAL_QUERY,
            "fixpoint_ms_pairs_f32": 1e3 * t_pairs_f32,
            "fixpoint_ms_witness_f32": 1e3 * t_wit_f32,
            "fixpoint_ms_pairs_packed": 1e3 * t_pairs_packed,
            "fixpoint_ms_witness_packed": 1e3 * t_wit_packed,
            "overhead_x_f32": t_wit_f32 / t_pairs_f32,
            "overhead_x_packed": t_wit_packed / t_pairs_packed,
        },
        "closure_fast_path": {
            "query": CLOSURE_QUERY,
            "n_states_general": ca_gen.n_states,
            "n_states_fast": ca_fast.n_states,
            "grid_steps_general": int(np.asarray(plan_gen.step).shape[0]),
            "grid_steps_fast": int(np.asarray(plan_fast.step).shape[0]),
            "fixpoint_ms_closure_general": 1e3 * t_gen,
            "fixpoint_ms_closure_fastpath": 1e3 * t_fast,
            "speedup_x": t_gen / t_fast,
            "bit_exact_vs_general": bit_exact,
        },
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)

    rows = [
        "witness,section,metric,value",
        f"witness,overhead,fixpoint_ms_pairs_f32,{1e3 * t_pairs_f32:.2f}",
        f"witness,overhead,fixpoint_ms_witness_f32,{1e3 * t_wit_f32:.2f}",
        f"witness,overhead,fixpoint_ms_pairs_packed,{1e3 * t_pairs_packed:.2f}",
        f"witness,overhead,fixpoint_ms_witness_packed,{1e3 * t_wit_packed:.2f}",
        f"witness,overhead,overhead_x_f32,{t_wit_f32 / t_pairs_f32:.3f}",
        f"witness,overhead,overhead_x_packed,{t_wit_packed / t_pairs_packed:.3f}",
        f"witness,closure,fixpoint_ms_general,{1e3 * t_gen:.2f}",
        f"witness,closure,fixpoint_ms_fastpath,{1e3 * t_fast:.2f}",
        f"witness,closure,speedup_x,{t_gen / t_fast:.3f}",
        f"witness,closure,bit_exact,{bit_exact}",
        f"witness,json,{out},written",
    ]
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
