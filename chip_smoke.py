"""Smoke run of the RPQ serving path on a TPU.

Builds the Alibaba statistical twin at its published defaults from
``--seed`` (``generators.alibaba_like()``: 50,000 nodes, ~328k edges, 216
labels), places it over 4 execution sites at replication 0.3 with the
probed network parameters (as ``examples/plan_and_serve_rpq.py`` does),
and answers Table-2 queries (q1, q6, q9, q11, two valid starts each)
through ``QueryService`` on each S2 backend in turn:

* ``reference`` — the ``shard_map`` gather/scatter executor;
* ``frontier_kernel`` over the bitpacked uint32 tile store;
* ``frontier_kernel_packed`` over the uint32 store;
* the same requests again through ``AsyncQueryService`` (packed);
* one ``semantics="witness"`` request, its path validated edge by edge
  (witness requests restage f32 tiles, so that phase stages only the
  query's slabs under ``tile_store_budget_bytes``).

Every answer set is compared with the PAA oracle
(``paa.answers_single_source``); a mismatch or an exception exits
non-zero.  Each phase prints one JSON line (backend, tile dtype, queries,
``answers_match``, compile and wall seconds, compile-cache directory,
staged tile bytes, device memory).  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--four-chips`` runs only the site-sharded path on a (4, 1)
("data", "model") mesh over the 4 sites: ``frontier_kernel_sharded``
answers against the global ``frontier_kernel`` answers and the oracle,
each site's arrays on their own chip, and per-site response meters
summing to the host meter.

It refuses to run when JAX's first device is not a TPU (including under
``JAX_PLATFORMS=cpu``).  Run from the repository root:

    python chip_smoke.py [--seed N] [--four-chips]
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import paa, planner, strategies, witness  # noqa: E402
from repro.dist import compat  # noqa: E402
from repro.graph import generators  # noqa: E402
from repro.graph.partition import distribute, random_overlay  # noqa: E402
from repro.graph.structure import LabeledGraph, to_device_graph  # noqa: E402
from repro.serve import AsyncQueryService, QueryService, ServeConfig  # noqa: E402

QUERY_NAMES = ("q1", "q6", "q9", "q11")
STARTS_PER_QUERY = 2
N_SITES = 4
# witness requests stage f32 tiles; this budget holds one query's slabs
# and keeps the rest of the ~9.3 GB f32 store off the device
WITNESS_BUDGET_BYTES = 1 << 30


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events.  The compile event wraps compile-or-fetch, so a cache hit
    counts the seconds it took to read the entry."""

    def __init__(self):
        self.counts = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compile_s"] += duration_secs
            self.counts["compiles"] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.counts.items()}


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass
class Request:
    name: str
    query: str
    ca: paa.CompiledAutomaton
    starts: np.ndarray
    oracle: list[set[int]]


@dataclasses.dataclass
class World:
    seed: int
    graph: LabeledGraph
    placement: object
    params: object
    mesh: object
    requests: list[Request]
    cache_dir: str
    meter: CompileMeter


def build_world(seed: int, mesh, cache_dir: str, meter: CompileMeter,
                graph: LabeledGraph | None = None) -> World:
    """The twin, its placement, the probed network and the requests with
    their oracle answers."""
    g = graph if graph is not None else generators.alibaba_like(seed=seed)
    net = random_overlay(150, 3.0, seed=seed + 1)
    params = planner.probe_network(
        net, distribute(g, 150, replication_rate=0.2, seed=seed + 1)
    )
    placement = distribute(g, N_SITES, replication_rate=0.3, seed=seed + 2)
    dg = to_device_graph(g)
    requests = []
    for name in QUERY_NAMES:
        query = generators.TABLE2_QUERIES[name]
        ca = paa.compile_query(query, g)
        starts = np.asarray(paa.valid_start_nodes(ca, g)[:STARTS_PER_QUERY], np.int32)
        check(len(starts) > 0, f"{name}: no valid start nodes")
        oracle = [
            set(np.nonzero(np.asarray(paa.answers_single_source(ca, dg, int(s))))[0].tolist())
            for s in starts
        ]
        requests.append(Request(name, query, ca, starts, oracle))
    print(json.dumps({
        "phase": "setup", "nodes": g.n_nodes, "edges": g.n_edges, "labels": g.n_labels,
        "sites": placement.n_sites, "replication_factor": placement.replication_factor,
        "mesh": dict(mesh.shape), "queries": list(QUERY_NAMES),
    }), flush=True)
    return World(seed, g, placement, params, mesh, requests, cache_dir, meter)


def make_service(world: World, backend: str, tile_dtype: str = "f32", budget=None):
    return QueryService(
        world.placement, world.mesh, world.params,
        config=ServeConfig(
            n_rollouts=600, seed=world.seed, s2_backend=backend,
            s2_tile_dtype=tile_dtype, tile_store_budget_bytes=budget,
        ),
    )


def check_answers(req: Request, ans, phase: str) -> None:
    check(ans.strategy == "S2", f"{phase} {req.name}: ran {ans.strategy}, not S2")
    for i, s in enumerate(req.starts):
        check(
            ans.answers[i] == req.oracle[i],
            f"{phase} {req.name} start {int(s)}: {len(ans.answers[i])} answers, "
            f"oracle has {len(req.oracle[i])}",
        )


def check_interpret(service: QueryService, backend: str, expect_interpret: bool) -> None:
    flags = service.exec_cache.interpret_flags()
    if backend != "reference":
        check(len(flags) > 0, f"{backend}: no fused executor was built")
    check(
        all(f == expect_interpret for f in flags),
        f"{backend}: executors resolved interpret={flags}, expected {expect_interpret}",
    )


def report(phase: str, world: World, service: QueryService, n_queries: int,
           t0: float, c0: dict) -> None:
    cfg = service.config
    mem = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": phase,
        "backend": cfg.s2_backend,
        "tile_dtype": cfg.s2_tile_dtype,
        "n_queries": n_queries,
        "answers_match": True,
        **world.meter.since(c0),
        "wall_s": time.perf_counter() - t0,
        "cache_dir": world.cache_dir,
        "staged_tile_bytes": service.plan_store.tile_store_stats()["bytes_by_dtype"],
        "device_bytes_in_use": mem.get("bytes_in_use"),
        "device_peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "device_bytes_limit": mem.get("bytes_limit"),
    }), flush=True)


def serve_phase(phase: str, world: World, backend: str, tile_dtype: str = "f32",
                expect_interpret: bool = False):
    """Answer every request once through ``QueryService.flush``."""
    t0, c0 = time.perf_counter(), dict(world.meter.counts)
    service = make_service(world, backend, tile_dtype)
    tickets = [(r, service.enqueue(r.query, r.starts, strategy="S2")) for r in world.requests]
    service.flush()
    answers = {}
    for r, t in tickets:
        ans = t.result()  # re-raises an executor failure
        check_answers(r, ans, phase)
        answers[r.name] = ans
    check_interpret(service, backend, expect_interpret)
    report(phase, world, service, len(tickets), t0, c0)
    return service, answers


async def _submit_all(service: QueryService, requests: list[Request]):
    async with AsyncQueryService(service) as aio:
        return await asyncio.gather(
            *(aio.submit(r.query, r.starts, strategy="S2") for r in requests)
        )


def async_phase(world: World, service: QueryService, expect_interpret: bool = False) -> None:
    """The same requests once more, through the asyncio front end."""
    t0, c0 = time.perf_counter(), dict(world.meter.counts)
    results = asyncio.run(_submit_all(service, world.requests))
    for r, ans in zip(world.requests, results):
        check_answers(r, ans, "async")
    check_interpret(service, service.config.s2_backend, expect_interpret)
    report("async", world, service, len(results), t0, c0)


def witness_phase(world: World, expect_interpret: bool = False) -> None:
    """One witness request on the fused backend; the reconstructed path
    must be real edges of the twin, accepted by the automaton, from the
    start to the target."""
    t0, c0 = time.perf_counter(), dict(world.meter.counts)
    req = next((r for r in world.requests if r.oracle[0]), None)
    check(req is not None, "witness: no request has an answer to explain")
    service = make_service(world, "frontier_kernel", "uint32", WITNESS_BUDGET_BYTES)
    start = req.starts[:1]
    ans = service.submit(req.query, start, strategy="S2", semantics="witness")
    check(ans.answers[0] == req.oracle[0], f"witness {req.name}: answers differ from the oracle")
    target = min(ans.answers[0])
    path = service.witness_path(ans, 0, target)
    ok, why = witness.validate_witness(path, world.graph)
    check(ok, f"witness {req.name}: {why}")
    check(
        path.nodes[0] == int(start[0]) and path.nodes[-1] == target,
        f"witness {req.name}: path runs {path.nodes[0]} -> {path.nodes[-1]}, "
        f"not {int(start[0])} -> {target}",
    )
    check(
        witness.nfa_accepts_symbols(ans.exec_ca, path.steps),
        f"witness {req.name}: the automaton rejects the path's labels",
    )
    check_interpret(service, "frontier_kernel", expect_interpret)
    report("witness", world, service, 1, t0, c0)


def check_site_placement(arr: jax.Array, sites: tuple[int, ...], mesh, s_local: int,
                         what: str) -> None:
    """Row ``r`` of a per-site stack belongs to site ``sites[r]``; it must
    sit (only) on the device at that site's position of the site axis."""
    site_devices = list(mesh.devices.reshape(mesh.shape["data"], -1)[:, 0])
    for shard in arr.addressable_shards:
        for row in range(*shard.index[0].indices(arr.shape[0])):
            want = site_devices[sites[row] // s_local]
            check(shard.device == want,
                  f"{what}: site {sites[row]} rows on {shard.device}, expected {want}")


def sharded_phases(world: World, expect_interpret: bool = False) -> None:
    """The paper's distribution model on the mesh: per-site grids under
    ``shard_map`` and the ``ppermute`` ring, against the global fused
    backend, the oracle and the host meter."""
    g, placement, mesh = world.graph, world.placement, world.mesh
    sh_service, sh_answers = serve_phase(
        "sharded", world, "frontier_kernel_sharded", "uint32", expect_interpret
    )
    _, gl_answers = serve_phase("global", world, "frontier_kernel", "uint32", expect_interpret)
    for r in world.requests:
        check(
            sh_answers[r.name].answers == gl_answers[r.name].answers,
            f"sharded {r.name}: answers differ from the global backend",
        )
    # every copy of an edge answers a broadcast, so the per-site meters
    # sum to the host meter run over the multiset union of site edges
    eids = np.concatenate(placement.site_edges)
    copies = paa.HostIndex(LabeledGraph(g.n_nodes, g.src[eids], g.lbl[eids], g.dst[eids], g.labels))
    for r in world.requests:
        exec_ca = planner.reduce_automaton(r.ca, planner.classify_query(r.query))
        for i, s in enumerate(r.starts):
            c = sh_answers[r.name].observed[i]
            host = strategies.s2_costs(exec_ca, copies, int(s))
            check(len(c.site_unicast_symbols) == placement.n_sites,
                  f"sharded {r.name}: {len(c.site_unicast_symbols)} site meters")
            check(sum(c.site_unicast_symbols) == host.unicast_symbols,
                  f"sharded {r.name} start {int(s)}: site meters sum to "
                  f"{sum(c.site_unicast_symbols)}, host meter {host.unicast_symbols}")
            check(c.broadcast_symbols == host.broadcast_symbols,
                  f"sharded {r.name} start {int(s)}: broadcast meter differs")
    s_local = placement.n_sites // mesh.shape["data"]
    stacks = strategies.site_sharding(mesh, sh_service.config.site_axes)
    arrays = sh_service.plan_store.site_device_arrays(
        placement, sh_service.stats_epoch, sharding=stacks
    )
    for k in ("src", "lbl", "dst", "mask"):
        check_site_placement(arrays[k], tuple(range(placement.n_sites)), mesh, s_local,
                             f"site array {k}")
    buckets = sh_service.plan_store.tile_buckets(
        placement, sh_service.config.s2_block_size, mesh.shape["data"],
        epoch=sh_service.stats_epoch, floor=sh_service.config.s2_bucket_floor,
        tile_dtype="uint32", sharding=stacks,
    )
    for b in buckets.buckets:
        check_site_placement(b.tiles, b.sites, mesh, s_local, f"tile bucket {b.n_tiles}")
    print(json.dumps({
        "phase": "sharded_checks", "answers_equal_global": True,
        "site_meters_equal_host": True, "sites_on_own_devices": True,
        "n_buckets": len(buckets.buckets),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the site-sharded path on a (4, 1) mesh")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, but JAX's first device is platform "
            f"{dev.platform!r} ({dev.device_kind}); not running"
        )
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, JAX sees {len(devices)}")
    cache_dir = compile_cache.enable()
    mesh = compat.make_mesh((n_chips, 1), ("data", "model"), devices=devices[:n_chips])
    world = build_world(args.seed, mesh, cache_dir, CompileMeter())

    if args.four_chips:
        sharded_phases(world)
    else:
        serve_phase("reference", world, "reference")
        serve_phase("frontier_kernel", world, "frontier_kernel", "uint32")
        packed, _ = serve_phase("packed", world, "frontier_kernel_packed", "uint32")
        async_phase(world, packed)
        del packed
        witness_phase(world)

    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }), flush=True)


if __name__ == "__main__":
    main()
