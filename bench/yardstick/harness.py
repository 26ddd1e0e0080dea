"""One run of one cell: build the deployment from the seed, warm every
shape the traffic uses, measure a window, drain, then compare every
answered request with the plain reference.

Everything that belongs to one deployment, one traffic mix or one
per-layer metric is data or a small reader found by name under
``bench/configs``, ``bench/traffic`` and ``bench/metrics``; this module
holds none of it.
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

from yardstick import hooks, placement, reference, tracing, traffic, twin

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
DRAIN_S = 60.0  # how long past the window's close an answer may still come
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _load_json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))

    def mine(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


def _load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader_path(metric: str, root: str = ROOT) -> str:
    """``bench/metrics/<metric>.py``, or else the reader of the name
    before the metric's last dot: ``flush_ms.closed`` and
    ``flush_ms.open`` are both read by ``flush_ms.py``."""
    for name in (metric, metric.rsplit(".", 1)[0]):
        path = os.path.join(root, "bench", "metrics", name + ".py")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no reader bench/metrics/{metric}.py for metric {metric!r}")


def load_reader(metric: str, root: str = ROOT):
    """A metric's reader: ``read(obs: Observations) -> float | None``
    (``None`` where the run holds nothing to read), and optionally
    ``SPANS``, the program calls it needs timed (see ``hooks``)."""
    return _load_module(reader_path(metric, root))


def load_generator(name: str, root: str = ROOT):
    """A graph generator, ``bench/graphs/<name>.py``:
    ``generate(seed: int, **params) -> twin.Graph``."""
    return _load_module(os.path.join(root, "bench", "graphs", name + ".py"))


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at the fixed ``.jax_cache`` of
    the checkout, whatever the environment says (a cache kept outside
    the checkout could be shared with another checkout's runs), with the
    program's own thresholds (``repro.compile_cache``)."""
    import jax

    from repro import compile_cache

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    compile_cache.enable()
    return path


def deployment_seeds(seed: int) -> dict[str, int]:
    """The deployment's sub-seeds from its configuration's ``seed``, as
    ``examples/plan_and_serve_rpq.py`` and ``chip_smoke.py`` draw them."""
    return {"graph": seed, "overlay": seed + 1, "probe_sites": seed + 1,
            "sites": seed + 2, "planner": seed}


# -- the deployment -------------------------------------------------------------


@dataclasses.dataclass
class World:
    graph: twin.Graph
    ref: reference.Evaluator
    service: object
    aio_config: object


def build_world(config: dict, mesh, control: bool = False) -> World:
    """The graph, its placement over sites, the probed network and the
    service, as the configuration states them (from its ``seed``: the
    deployment is the same in every run; the run's seed draws only the
    traffic); with ``control`` the configuration's ``control`` overrides
    are applied to the service."""
    from repro.core import planner
    from repro.graph.partition import OverlayNetwork, Placement
    from repro.graph.structure import LabeledGraph
    from repro.serve import AioConfig, QueryService, ServeConfig

    sub = deployment_seeds(int(config["seed"]))
    params = dict(config["graph"])
    g = load_generator(params.pop("generator")).generate(seed=sub["graph"], **params)
    lg = LabeledGraph(g.n_nodes, g.src, g.lbl, g.dst, g.labels)

    def place(n_sites: int, rate: float, s: int):
        sites = placement.distribute(g.n_edges, n_sites, replication_rate=rate, seed=s)
        return Placement(lg, sites.n_sites, sites.site_edges, sites.replication)

    net = config["network"]
    adj_src, adj_dst = placement.random_overlay(net["n_peers"], net["mean_degree"], seed=sub["overlay"])
    probe = place(net["n_peers"], net["replication_rate"], sub["probe_sites"])
    network = planner.probe_network(OverlayNetwork(net["n_peers"], adj_src, adj_dst), probe)
    del probe
    sites = config["placement"]
    placed = place(sites["n_sites"], sites["replication_rate"], sub["sites"])
    serve = dict(config["serve"])
    if control:
        serve.update(config["control"]["serve"])
    service = QueryService(placed, mesh, network, config=ServeConfig(seed=sub["planner"], **serve))
    return World(
        graph=g,
        ref=reference.Evaluator(g.n_nodes, g.src, g.lbl, g.dst, g.labels),
        service=service,
        aio_config=AioConfig(**config.get("aio", {})),
    )


# -- the measured loops ---------------------------------------------------------


@dataclasses.dataclass
class Outcome:
    req: traffic.Request
    t_due: float  # host clock: when the request was due (open) or sent (closed)
    t_sent: float
    t_done: float | None = None
    answers: object = None  # the program's Answers
    error: str | None = None


async def _submit(aio, req: traffic.Request, out: Outcome) -> None:
    from repro.serve import AdmissionRejected

    try:
        out.answers = await aio.submit(
            req.query, req.starts, tenant=req.tenant, slo=req.slo, strategy=req.strategy,
            semantics=req.semantics,
        )
    except AdmissionRejected as e:
        out.error = f"refused: {e.reason}"
    except Exception as e:  # noqa: BLE001 — a failed request is counted, not fatal
        out.error = f"{type(e).__name__}: {e}"
    out.t_done = time.perf_counter()


async def _closed(aio, clients, t_open: float, seconds: float) -> list[Outcome]:
    outcomes: list[Outcome] = []
    t_close = t_open + seconds

    async def caller(seq) -> None:
        while time.perf_counter() < t_close:
            req = next(seq)
            now = time.perf_counter()
            out = Outcome(req, now, now)
            outcomes.append(out)
            await _submit(aio, req, out)

    tasks = [asyncio.ensure_future(caller(seq)) for seq in clients]
    await asyncio.wait(tasks, timeout=seconds + DRAIN_S)
    for t in tasks:
        t.cancel()
    return outcomes


async def _open(aio, schedule, t_open: float, seconds: float) -> list[Outcome]:
    outcomes: list[Outcome] = []
    tasks = []
    for req in schedule:
        t_due = t_open + req.due
        delay = t_due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        out = Outcome(req, t_due, time.perf_counter())
        outcomes.append(out)
        tasks.append(asyncio.ensure_future(_submit(aio, req, out)))
    left = t_open + seconds + DRAIN_S - time.perf_counter()
    if tasks:
        await asyncio.wait(tasks, timeout=max(left, 0.0))
    for t in tasks:
        t.cancel()
    return outcomes


async def serve(service, aio_config, plan: traffic.Traffic, seconds: float,
                 on_open=None, on_close=None) -> tuple[list[Outcome], float, dict]:
    from repro.serve import AsyncQueryService

    aio = AsyncQueryService(service, aio_config)
    await aio.start()
    try:
        if on_open:
            on_open()
        t_open = time.perf_counter()
        if plan.loop == "closed":
            run = _closed(aio, plan.clients, t_open, seconds)
        else:
            run = _open(aio, plan.schedule, t_open, seconds)
        if on_close:  # at the close; the call after the run is a no-op then
            asyncio.get_running_loop().call_later(seconds, on_close)
        outcomes = await run
        if on_close:
            on_close()
        return outcomes, t_open, aio.aio_stats()
    finally:
        await aio.stop()


def warm_up(service, aio_config, plan: traffic.Traffic) -> list[Outcome]:
    """Run each prebuilt request once and forget its plan, then plan and
    run each warm request once, alone through the service and then
    together through the async front end, so that every executor and
    answer path the window takes is compiled and cached.  Returns the
    first pass's answers, which are compared with the window's (the only
    S1 answers of a run where the planner picks S2 throughout)."""
    from repro.serve import plancache

    warm = []
    for req in plan.prebuild + plan.warm:
        if req is (plan.warm[0] if plan.warm else None):
            # forget the prebuilt classes' plans: they meet the planner
            # inside the window, with their executors already compiled
            service.plan_cache = plancache.PlanCache(service.config.plan_cache_size)
        t = time.perf_counter()
        ans = service.submit(req.query, req.starts, strategy=req.strategy, semantics=req.semantics)
        warm.append(Outcome(req, t, t, time.perf_counter(), ans))

    async def together():
        from repro.serve import AsyncQueryService

        async with AsyncQueryService(service, aio_config) as aio:
            await asyncio.gather(*(
                aio.submit(r.query, r.starts, tenant=r.tenant, slo=r.slo, strategy=r.strategy,
                           semantics=r.semantics)
                for r in plan.warm
            ))

    if plan.warm:
        asyncio.run(together())
    return warm


# -- the comparison that decides `correct` ----------------------------------------


def compare(world: World, outcomes: list[Outcome]) -> dict:
    """Every answered request's answer sets against the reference, start
    by start.  Returns counts of requests compared, wrong, and never
    answered: failed, lost, or refused at admission (a refused request
    is an answer that never comes, so shedding load cannot read as a
    faster system)."""
    answered = [o for o in outcomes if o.answers is not None]
    by_query: dict[str, list[Outcome]] = {}
    for o in answered:
        by_query.setdefault(o.req.query, []).append(o)
    wrong = 0
    wrong_examples = []
    for query, outs in by_query.items():
        starts = np.unique(np.concatenate([np.asarray(o.req.starts) for o in outs]))
        ref = dict(zip(starts.tolist(), world.ref.answers(query, starts)))
        for o in outs:
            got = o.answers.answers
            bad = len(got) != len(o.req.starts) or any(
                set(got[i]) != set(ref[int(s)].tolist()) for i, s in enumerate(o.req.starts)
            )
            if bad:
                wrong += 1
                if len(wrong_examples) < 3:
                    wrong_examples.append(f"{o.answers.strategy} {query!r}")
    failed = [o for o in outcomes if o.answers is None]
    return {
        "compared": len(answered),
        "wrong": wrong,
        "unanswered": len(failed),
        "wrong_examples": wrong_examples,
        "errors": sorted({o.error or "no answer" for o in failed})[:3],
    }


# -- one run --------------------------------------------------------------------




@dataclasses.dataclass
class Observations:
    """What a metric's reader (``bench/metrics/<name>.py``,
    ``read(obs) -> float | None``) may read of a run: the requests and
    their times on the host clock, the set-up time, and in a traced run
    the front end's counters, the declared spans and the reduced trace."""

    outcomes: list[Outcome]  # every request sent in the run
    t_open: float  # host clock at the window's open
    seconds: float
    setup_s: float
    aio_stats: dict | None = None  # AsyncQueryService.aio_stats() of the window's front end
    spans: hooks.Spans | None = None
    trace: tracing.Reduced | None = None

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds

    def due_in_window(self) -> list[Outcome]:
        return [o for o in self.outcomes if o.t_due <= self.t_close]

    def completed_in_window(self) -> list[Outcome]:
        return [o for o in self.outcomes if o.answers is not None and o.t_done <= self.t_close]

    def latencies_ms(self) -> list[float]:
        """From due time to answer, for every request due in the window
        and answered (one never answered makes the run not correct)."""
        return [(o.t_done - o.t_due) * 1e3 for o in self.due_in_window() if o.answers is not None]


def _percentile(values: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(values), q)) if values else float("nan")


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"needs a TPU, but JAX's first device is {devices[0].platform!r} "
            f"({devices[0].device_kind})"
        )
    if len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chips, JAX sees {len(devices)}")
    return devices


def peak_of(kind: str) -> dict:
    peaks = _load_json(os.path.join(BENCH_DIR, "yardstick", "peaks.json"))["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/yardstick/peaks.json")
    return peaks[kind]


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, control: bool = False, log=None) -> dict:
    """One run; returns the result line's object.  With ``trace`` the
    metrics are the cell's per-layer ones, else its end-to-end ones,
    each computed by its reader from the run's ``Observations``."""
    import jax

    from repro.dist import compat

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    if require_chip:
        devices = check_devices(cell.chips)
        peak_of(devices[0].device_kind)
    else:
        devices = jax.devices()
    used = devices[: cell.chips]
    metrics_wanted = cell.per_layer if trace else cell.end_to_end
    readers = {m["name"]: load_reader(m["name"]) for m in metrics_wanted}
    meter = hooks.CompileMeter()
    mesh = compat.make_mesh((cell.chips, 1), ("data", "model"), devices=used)
    t_build = time.perf_counter()
    world = build_world(cell.config, mesh, control=control)
    service = world.service
    plan = traffic.build(cell.mix, world.graph, world.ref, seed, seconds)
    t_warm = time.perf_counter()
    warm = warm_up(service, world.aio_config, plan)
    setup_s = time.perf_counter() - t_start
    c_open = dict(meter.counts)
    log(json.dumps({"setup": {
        "setup_s": setup_s, "start_s": t_build - t_start, "build_s": t_warm - t_build,
        "warm_s": t_start + setup_s - t_warm, "warm_requests": len(plan.warm), **meter.counts}}))

    spans = hooks.Spans()
    plan_stats0 = service.plan_cache.stats()
    trace_ctl = {}
    if trace:
        hooks.install(spans, service, hooks.declared(readers.values()))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

        def on_open():
            # no Python-call tracing and only annotation-level host events:
            # the defaults doubled the planner's host time on a CPU
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            trace_ctl["ann"] = jax.profiler.TraceAnnotation("bench." + tracing.WINDOW_SPAN)
            trace_ctl["ann"].__enter__()

        def on_close():  # idempotent
            if "ann" in trace_ctl:
                trace_ctl.pop("ann").__exit__(None, None, None)
                jax.profiler.stop_trace()
    else:
        on_open = on_close = None
    try:
        outcomes, t_open, aio_stats = asyncio.run(
            serve(service, world.aio_config, plan, seconds, on_open, on_close)
        )
    finally:
        spans.restore()
    in_window = meter.since(c_open)
    mem = [d.memory_stats() or {} for d in used]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    plan_stats1 = service.plan_cache.stats()

    obs = Observations(outcomes, t_open, seconds, setup_s, aio_stats, spans)
    lag_ms = [(o.t_sent - o.t_due) * 1e3 for o in outcomes]
    strat: dict[str, int] = {}
    for o in outcomes:
        if o.answers is not None:
            strat[o.answers.strategy] = strat.get(o.answers.strategy, 0) + 1
    log(json.dumps({
        "window": {"seconds": seconds, "attempted": len(outcomes), "due": len(obs.due_in_window()),
                   "completed_in_window": len(obs.completed_in_window()),
                   "last_answer_s": max((o.t_done for o in outcomes if o.answers is not None),
                                        default=obs.t_close) - t_open,
                   "refused": sum(1 for o in outcomes if (o.error or "").startswith("refused")),
                   "generator_lag_ms_p50": _percentile(lag_ms, 50),
                   "generator_lag_ms_max": max(lag_ms) if lag_ms else 0.0,
                   "strategy_split": strat, "compiles_in_window": in_window["compiles"],
                   "compile_s_in_window": in_window["compile_s"],
                   "plan_cache_misses": plan_stats1["misses"] - plan_stats0["misses"],
                   "lane_fill_ratio": aio_stats["batch_window"]["fill_ratio"]},
    }))

    if trace:
        obs.trace = tracing.reduce(tracing.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    for m in metrics_wanted:
        value = readers[m["name"]].read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's state goes before the reference runs
    del service
    world.service = None
    t_ref = time.perf_counter()
    check = compare(world, outcomes + warm)
    log(f"reference_s {time.perf_counter() - t_ref:.3f}")
    correct = check["wrong"] == 0 and check["unanswered"] == 0 and check["compared"] > 0
    limits = {
        "wrong_requests": {"value": check["wrong"], "limit": 0},
        "unanswered_requests": {"value": check["unanswered"], "limit": 0},
    }
    for name, v in limits.items():
        log(f"compared {name} {v['value']} limit {v['limit']}")
    log(f"compared requests_compared {check['compared']} (window {len(outcomes)}, "
        f"set-up {len(warm)}) limit >0")
    if check["wrong_examples"] or check["errors"]:
        log(f"wrong: {check['wrong_examples']} errors: {check['errors']}")

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    if trace:
        device["busy_s"] = obs.trace.busy_s
        device["window_s"] = obs.trace.window_s
    result = {
        "correct": bool(correct),
        "attempted": len(outcomes),
        "failed": sum(1 for o in outcomes if o.answers is None),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = obs.trace.breakdown()
    result["compared"] = limits
    return result
