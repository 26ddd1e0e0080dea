"""Roofline aggregation: reads dryrun_results.json and prints the
per-(arch × shape × mesh) three-term roofline table (§Roofline) — plus
the frontier **memory roofline** (``run_packed``): f32 query stacking vs
bitpacked uint32 lane words at Q ∈ {8, 64, 256}, and the chunked
Stage-A staging sweep on a ≥100k-edge graph.

``run_packed`` measures three things and writes
``BENCH_frontier_packed.json`` (the ``packed`` subset of
``benchmarks.run``, regression-gated on its ``fixpoint_ms*`` leaves):

* **frontier bytes** — the fixpoint frontier operand one Q-query batch
  needs: f32 stacking pays 4 bytes per (state, lane, node) across
  ``ceil(Q/8)`` sequential 8-lane chunks; the packed path pays one bit
  per lane inside the same 8 uint32 word rows — a 32× footprint drop at
  Q=256.
* **multi-query fixpoint latency** — ``multi_query_reach`` (f32) vs
  ``multi_query_reach_packed`` on the same plan: at Q=64 the f32 path
  runs 8 device-resident fixpoints back-to-back, the packed path one.
* **staging peak memory** — one-shot ``stage_graph`` vs chunked
  (``chunk_edges``) on a ≥100k-edge generator graph: tracemalloc peak
  *transient* host bytes (peak minus the retained staged tiles), plus a
  byte-identity check of the staged artifacts.
* **tile-store dtype sweep** — f32 vs bitpacked uint32 Stage-A staging
  at the 100k- and 400k-edge points: staged tile-store bytes per dtype
  (the acceptance target is ≥8×, measured 32× at block 128), the fused
  boolean fixpoint latency on each store (``fixpoint_ms_tiles_*`` rows,
  regression-gated), and an out-of-core run that replays a label stream
  through a :class:`~repro.core.plans.GraphPlanStore` under a byte
  budget a third of the full store (``--budget-bytes`` overrides),
  recording the spill/reload counts and the resident ceiling.
"""

from __future__ import annotations

import json
import os
import time
import tracemalloc


def run(path: str = "dryrun_results.json") -> list[str]:
    if not os.path.exists(path):
        return ["roofline,SKIPPED (run `python -m repro.launch.dryrun --mesh both` first)"]
    with open(path) as f:
        results = json.load(f)
    rows = [
        "roofline,arch,shape,mesh,ok,peak_GiB_dev,compute_ms,memory_ms,"
        "collective_ms,bottleneck,useful_flops_ratio"
    ]
    for key in sorted(results):
        r = results[key]
        arch, shape, mesh = key.split("|")
        if not r.get("ok"):
            rows.append(f"roofline,{arch},{shape},{mesh},FAIL,,,,,{r.get('error','')[:60]},")
            continue
        roof = r["roofline"]
        ufr = r.get("useful_flops_ratio")
        rows.append(
            f"roofline,{arch},{shape},{mesh},ok,"
            f"{r['memory']['peak_estimate_bytes'] / 2**30:.2f},"
            f"{roof['compute_s'] * 1e3:.2f},{roof['memory_s'] * 1e3:.2f},"
            f"{roof['collective_s'] * 1e3:.2f},{roof['bottleneck']},"
            f"{'' if ufr is None else f'{ufr:.2f}'}"
        )
    return rows


PACKED_QUERY = "(l0|l1)* l2 .^-1"  # union-star + wildcard-inverse
PACKED_JSON = "BENCH_frontier_packed.json"


def _best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_packed(
    n_nodes: int = 128,
    n_edges: int = 900,
    n_labels: int = 5,
    block: int = 64,
    repeats: int = 3,
    big_nodes: int = 512,
    big_edges: int = 400_000,
    chunk_edges: int = 50_000,
    out: str = PACKED_JSON,
    seed: int = 0,
    interpret: bool | None = None,
    budget_bytes: int | None = None,
) -> list[str]:
    import numpy as np

    import jax.numpy as jnp

    from benchmarks.common import bench_env
    from repro.core import paa
    from repro.core.automaton import FWD, INV
    from repro.core.plans import GraphPlanStore
    from repro.graph.generators import random_labeled_graph
    from repro.kernels.frontier import ops as fops
    from repro.kernels.frontier.frontier import resolve_interpret

    interpret = resolve_interpret(interpret)

    g = random_labeled_graph(n_nodes, n_edges, n_labels, seed=seed)
    bg = fops.make_blocked_graph(g, block_size=block)
    ca = paa.compile_query(PACKED_QUERY, g)
    plan = fops.build_level_plan(ca, bg)
    v_pad = plan.v_pad

    rng = np.random.default_rng(seed)
    result = {
        "benchmark": "frontier_packed",
        "env": bench_env(),
        "query": PACKED_QUERY,
        "n_nodes": n_nodes,
        "n_edges": n_edges,
        "n_labels": n_labels,
        "block_size": block,
        "n_states": ca.n_states,
        "interpret": interpret,
    }
    rows = ["packed,metric,value"]

    # ---- frontier bytes + fixpoint latency at Q in {8, 64, 256} ----------
    for q in (8, 64, 256):
        masks = np.zeros((q, n_nodes), np.float32)
        masks[np.arange(q), rng.choice(n_nodes, size=q)] = 1.0

        # f32 stacking: ceil(Q/8) sequential chunks, each a full
        # (n_states·8, v_pad) f32 frontier; packed: ceil(Q/256) chunks of
        # the same shape in uint32 lane words (1 bit per lane)
        chunks_f32 = -(-q // fops.QPAD)
        chunks_pk = -(-q // fops.QPACK)
        bytes_f32 = chunks_f32 * ca.n_states * fops.QPAD * v_pad * 4
        bytes_pk = chunks_pk * ca.n_states * fops.QPAD * v_pad * 4
        result[f"frontier_bytes_f32_q{q}"] = bytes_f32
        result[f"frontier_bytes_packed_q{q}"] = bytes_pk
        result[f"frontier_bytes_ratio_q{q}"] = bytes_f32 / bytes_pk

        def fx_f32():
            fops.multi_query_reach(ca, bg, masks, interpret=interpret, plan=plan)

        def fx_pk():
            fops.multi_query_reach_packed(ca, bg, masks, interpret=interpret, plan=plan)

        fx_f32(), fx_pk()  # warm the shared fixpoint traces
        a_f32 = fops.multi_query_reach(ca, bg, masks, interpret=interpret, plan=plan)
        a_pk = fops.multi_query_reach_packed(
            ca, bg, masks, interpret=interpret, plan=plan
        )
        if not (a_f32 == a_pk).all():
            raise AssertionError(f"packed != f32 answers at Q={q}")
        t_f32 = _best(fx_f32, repeats)
        t_pk = _best(fx_pk, repeats)
        result[f"fixpoint_ms_f32_q{q}"] = 1e3 * t_f32
        result[f"fixpoint_ms_packed_q{q}"] = 1e3 * t_pk
        result[f"throughput_ratio_q{q}"] = t_f32 / t_pk
        for k in (
            f"frontier_bytes_ratio_q{q}",
            f"fixpoint_ms_f32_q{q}",
            f"fixpoint_ms_packed_q{q}",
            f"throughput_ratio_q{q}",
        ):
            rows.append(f"packed,{k},{result[k]:.4f}")

    # ---- chunked Stage-A staging sweep on a >=100k-edge graph ------------
    big = random_labeled_graph(big_nodes, big_edges, 2, seed=seed + 1)

    def stage_oneshot():
        fops.reset_build_counters()
        return fops.stage_graph(big, 128)

    def stage_chunked():
        fops.reset_build_counters()
        return fops.stage_graph(big, 128, chunk_edges=chunk_edges)

    stage_oneshot()  # touch allocator pools once before measuring
    tracemalloc.start()
    tracemalloc.reset_peak()
    s_one = stage_oneshot()
    _, peak_one = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    tracemalloc.start()
    tracemalloc.reset_peak()
    s_chk = stage_chunked()
    _, peak_chk = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    chunks_used = int(fops.BUILD_COUNTERS["staging_chunks"])

    staged_bytes = int(np.asarray(s_one.tiles).nbytes)
    if not (np.asarray(s_one.tiles) == np.asarray(s_chk.tiles)).all():
        raise AssertionError("chunked staging is not byte-identical")
    result.update(
        {
            "staging_n_nodes": big_nodes,
            "staging_n_edges": big_edges,
            "staging_chunk_edges": chunk_edges,
            "staging_chunks": chunks_used,
            "staged_tile_bytes": staged_bytes,
            # peak traced bytes beyond the retained staged tiles: the
            # per-edge scratch the packing needed
            "staging_transient_bytes_oneshot": int(peak_one) - staged_bytes,
            "staging_transient_bytes_chunked": int(peak_chk) - staged_bytes,
        }
    )
    result["staging_transient_ratio"] = max(
        result["staging_transient_bytes_oneshot"], 1
    ) / max(result["staging_transient_bytes_chunked"], 1)

    # isolated per-label pack: the per-edge scratch chunking bounds,
    # without the (identical-on-both-paths) store concat copy
    from repro.kernels.frontier.ref import pack_blocks, pack_blocks_chunked

    src, dst = big.edges_with_label(0)

    def pack_one():
        return pack_blocks(src, dst, big.n_nodes, 128)

    def pack_chk():
        return pack_blocks_chunked(src, dst, big.n_nodes, 128, chunk_edges)

    pack_one()
    tracemalloc.start()
    tracemalloc.reset_peak()
    t_one = pack_one()[0]
    _, ppeak_one = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    tracemalloc.start()
    tracemalloc.reset_peak()
    t_chk = pack_chk()[0]
    _, ppeak_chk = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    tile_bytes = int(t_one.nbytes)
    result["pack_label_edges"] = int(len(src))
    result["pack_scratch_bytes_oneshot"] = int(ppeak_one) - tile_bytes
    result["pack_scratch_bytes_chunked"] = int(ppeak_chk) - tile_bytes
    result["pack_scratch_ratio"] = max(
        result["pack_scratch_bytes_oneshot"], 1
    ) / max(result["pack_scratch_bytes_chunked"], 1)
    del t_one, t_chk

    for k in (
        "staging_n_edges", "staging_chunks", "staged_tile_bytes",
        "staging_transient_bytes_oneshot", "staging_transient_bytes_chunked",
        "staging_transient_ratio", "pack_label_edges",
        "pack_scratch_bytes_oneshot", "pack_scratch_bytes_chunked",
        "pack_scratch_ratio",
    ):
        rows.append(f"packed,{k},{result[k]:.4f}")

    # ---- tile-store dtype sweep: f32 vs bitpacked uint32 -----------------
    # staged bytes + fused boolean fixpoint latency on each store, at the
    # 100k- and (by default) 400k-edge points
    for sweep_edges in (100_000, big_edges):
        gl = random_labeled_graph(big_nodes, sweep_edges, n_labels, seed=seed + 2)
        ca_l = paa.compile_query(PACKED_QUERY, gl)
        tag = f"e{sweep_edges // 1000}k"
        staged = {
            dt: fops.stage_graph(gl, 128, tile_dtype=dt)
            for dt in ("f32", "uint32")
        }
        for dt, s in staged.items():
            result[f"staged_tile_bytes_{dt}_{tag}"] = int(s.tile_store_bytes)
        result[f"staged_bytes_ratio_{tag}"] = (
            staged["f32"].tile_store_bytes / staged["uint32"].tile_store_bytes
        )

        masks = np.zeros((fops.QPAD, big_nodes), np.float32)
        masks[np.arange(fops.QPAD), rng.choice(big_nodes, size=fops.QPAD)] = 1.0
        visited = {}
        for dt, s in staged.items():
            plan_dt = fops.build_level_schedule(ca_l, s)
            f0 = jnp.asarray(fops.stack_start_masks(plan_dt, ca_l.start, masks))

            def fx(plan_dt=plan_dt, f0=f0):
                return np.asarray(
                    fops.reach_fixpoint(plan_dt, f0, interpret=interpret)
                )

            visited[dt] = fx() > 0  # warm the trace; keep for the identity check
            result[f"fixpoint_ms_tiles_{dt}_{tag}"] = 1e3 * _best(fx, repeats)
        if not (visited["f32"] == visited["uint32"]).all():
            raise AssertionError(f"uint32 store != f32 answers at {tag}")
        for k in (
            f"staged_tile_bytes_f32_{tag}", f"staged_tile_bytes_uint32_{tag}",
            f"staged_bytes_ratio_{tag}",
            f"fixpoint_ms_tiles_f32_{tag}", f"fixpoint_ms_tiles_uint32_{tag}",
        ):
            rows.append(f"packed,{k},{result[k]:.4f}")

    # ---- out-of-core: label stream under a tight slab-cache budget -------
    # replay every (direction, label) slab twice through a budgeted
    # GraphPlanStore — the second pass re-touches evicted slabs, so both
    # the spill and the reload paths are on the measured clock
    full_u32 = staged["uint32"]  # the 400k-point store from the sweep above
    tight = budget_bytes if budget_bytes is not None else full_u32.tile_store_bytes // 3
    store = GraphPlanStore()  # fresh: tile_store_stats sees only the slab cache
    fops.reset_build_counters()
    t0 = time.perf_counter()
    for lid in list(range(n_labels)) * 2:
        store.staged_graph(
            gl, 128, tile_dtype="uint32", budget_bytes=tight,
            keys=((FWD, lid), (INV, lid)),
        )
    stream_s = time.perf_counter() - t0
    ts = store.tile_store_stats()
    result.update(
        {
            "tile_budget_bytes": int(tight),
            "tile_budget_full_bytes": int(full_u32.tile_store_bytes),
            "tile_budget_spills": int(fops.BUILD_COUNTERS["spills"]),
            "tile_budget_reloads": int(fops.BUILD_COUNTERS["reloads"]),
            "tile_budget_resident_bytes": int(ts["bytes_by_dtype"]["uint32"]),
            "tile_budget_stream_ms": 1e3 * stream_s,
        }
    )
    for k in (
        "tile_budget_bytes", "tile_budget_full_bytes", "tile_budget_spills",
        "tile_budget_reloads", "tile_budget_resident_bytes",
        "tile_budget_stream_ms",
    ):
        rows.append(f"packed,{k},{result[k]:.4f}")

    with open(out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    rows.append(f"packed,json,{out}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
