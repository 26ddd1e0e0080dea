"""The one traffic generator: it reads a mix's parameters from its data
file (``bench/traffic/<mix>.json``) and draws the requests from the seed.

Keys of a mix file:

``loop``
    ``"closed"``: ``clients`` callers, each sending its next request when
    the last one is answered.  ``"open"``: requests due on a schedule,
    ``rate_qps`` of them a second, whatever the system does.
``arrivals``
    Open loop only; every kind is conditioned on its count
    (``round(rate_qps * seconds)`` requests), so every seed offers the
    same load.  ``"poisson"``: sorted uniform times over the window.
    ``{"kind": "on_off", "on_s": a, "off_s": b}``: sorted uniform times
    inside the on periods (the window opens with one), so a burst runs at
    ``(a + b) / a`` times the mean rate and nothing is due between bursts.
``schedule_seed``
    Open loop only, optional.  Where a mix states one, the schedule
    (arrival times, SLO classes, semantics, and the order of a table2 or
    list source) is drawn from it and is the same in every run; the run's
    seed then draws only which tenant each request names (a shuffled
    round robin, or the Zipf draw), which admission's token buckets make
    no work of while they do not bind.  Without one, all of it is drawn from the run's seed.  Where
    the stream holds a stall, the tail reads how many requests fall due
    in it and how many batching lanes (one per SLO class and signature)
    they fill as it drains: a schedule drawn anew each run moves a p95
    more than the system does.
``queries``
    Where the requests' queries and starts come from:

    ``{"source": "table2", "names": [...]}``: the paper's Table-2 queries;
    ``{"source": "list", "queries": [...]}``: regular expressions over the
    configuration's labels.  Each query runs over all its valid start
    nodes, and the requests cycle through seeded permutations of the
    queries (one sequence per caller, or one for an open loop).

    ``{"source": "seed_path", "stream_seed": s, ...}``: the seed-path
    stream of ``yardstick.workloads`` with the given ``WorkloadConfig``
    fields, drawn from ``stream_seed``: the same requests in the same
    order in every run (a stream drawn from the run's seed would change
    the work from seed to seed: one seed's cold classes plan in 0.02 s,
    another's in 12 s).  An open loop takes the stream's first
    ``round(rate_qps * seconds)`` requests; a closed loop's caller ``c``
    of ``C`` cycles through every ``C``-th of the first ``n_queries``
    (default 1000).
``tenants``
    A number: that many tenants, assigned round-robin by caller or by
    request.  ``{"n": k, "zipf": s}``: ``k`` tenants drawn from the seed,
    tenant ``r`` (from 0) with weight ``(r + 1) ** -s``.
``slo``
    Share of requests per SLO class; an exact split, shuffled by the
    seed, over requests (open) or callers (closed).
``semantics``
    Optional, shares of answer semantics (``"pairs"``, ``"witness"``),
    split as ``slo`` is; left out, every request takes the service's
    default.
``strategy``
    ``"S1"``, ``"S2"`` or ``null`` (the planner chooses).
``warm``
    ``"all"``: every distinct query (and semantics) is planned and run
    once in set-up.  ``"hot"``: only the seed-path stream's hot classes
    are; the cold ones meet the planner inside the window.  Their
    executors are built in set-up all the same (``prebuild``), so that
    nothing compiles in the window: each cold class runs once, and then
    the plan cache is emptied.
``warm_forced``
    Strategies each run once more in set-up on the first warm request,
    forced, so that a path the planner may pick for a cold class inside
    the window (the S1 gather's one shape) is compiled too.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterable, Iterator

import numpy as np

from yardstick import twin, workloads
from yardstick.reference import Evaluator

CLASS_SOURCES = ("table2", "list")


@dataclasses.dataclass(frozen=True)
class Request:
    query: str
    starts: np.ndarray
    tenant: str
    slo: str
    strategy: str | None
    due: float = 0.0  # open loop: seconds after the window opens
    hot: bool = True
    semantics: str | None = None


@dataclasses.dataclass
class Traffic:
    loop: str
    warm: list[Request]  # run once each in set-up
    schedule: list[Request]  # open loop: every request due in the window
    clients: list[Iterator[Request]] | None = None  # closed loop: per caller
    prebuild: list[Request] = dataclasses.field(default_factory=list)  # run, plans forgotten


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), stream])


def _split(shares: dict | None, n: int, rng: np.random.Generator) -> list:
    """An exact split of ``n`` by ``shares``, shuffled; ``None`` each
    where the mix gives no shares (and the generator is not drawn)."""
    if not shares:
        return [None] * n
    labels: list = []
    for cls, share in shares.items():
        labels += [cls] * int(round(share * n))
    labels = (labels + [next(iter(shares))] * n)[:n]
    rng.shuffle(labels)
    return labels


def _tenants(mix: dict, n: int, seed: int, shuffled: bool = False) -> list[str]:
    t = mix["tenants"]
    if isinstance(t, int):
        names = [f"tenant-{i % t}" for i in range(n)]
        if shuffled:
            _rng(seed, 6).shuffle(names)
        return names
    k = int(t["n"])
    weight = np.arange(1, k + 1, dtype=np.float64) ** -float(t["zipf"])
    return [f"tenant-{i}" for i in _rng(seed, 5).choice(k, size=n, p=weight / weight.sum())]


def _due(mix: dict, n: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    if isinstance(kind, dict) and kind.get("kind") == "on_off":
        on, period = float(kind["on_s"]), float(kind["on_s"]) + float(kind["off_s"])
        full = int(seconds // period)
        on_total = full * on + min(seconds - full * period, on)
        u = np.sort(rng.uniform(0.0, on_total, n))
        return (u // on) * period + u % on
    raise ValueError(f"unknown arrivals {kind!r}")


def _classes(q: dict, ref: Evaluator) -> list[tuple[str, np.ndarray]]:
    """Each query of a table2 or list source with its valid starts; a
    query with none is left out."""
    if q["source"] == "table2":
        queries = [twin.TABLE2_QUERIES[name] for name in q["names"]]
    else:
        queries = list(q["queries"])
    classes = [(query, ref.valid_starts(query)) for query in queries]
    return [(query, starts) for query, starts in classes if len(starts)]


def _cycle(classes: list, rng: np.random.Generator) -> Iterator[tuple[str, np.ndarray, bool]]:
    while True:
        for k in rng.permutation(len(classes)):
            query, starts = classes[k]
            yield query, starts, True


def _stream(q: dict, graph: twin.Graph, n: int) -> list[tuple[str, np.ndarray, bool]]:
    fields = {k: v for k, v in q.items() if k not in ("source", "stream_seed", "n_queries")}
    stream = workloads.generate(
        graph, workloads.WorkloadConfig(n_queries=n, seed=int(q["stream_seed"]), **fields)
    )
    return [(w.query, w.starts, w.hot) for w in stream]


def _caller(items: Iterable, **fields) -> Iterator[Request]:
    for query, starts, hot in items:
        yield Request(query, starts, hot=hot, **fields)


def _warm(mix: dict, candidates: list[Request]) -> tuple[list[Request], list[Request]]:
    warm, prebuild, seen = [], [], set()
    for r in candidates:
        if (r.query, r.semantics) in seen:
            continue
        seen.add((r.query, r.semantics))
        (warm if mix.get("warm", "all") == "all" or r.hot else prebuild).append(r)
    if warm:
        warm += [dataclasses.replace(warm[0], strategy=s) for s in mix.get("warm_forced", [])]
    return warm, prebuild


def build(mix: dict, graph: twin.Graph, ref: Evaluator, seed: int, seconds: float) -> Traffic:
    q = mix["queries"]
    if q["source"] not in CLASS_SOURCES + ("seed_path",):
        raise ValueError(f"unknown query source {q['source']!r}")
    strategy = mix.get("strategy")
    classes = _classes(q, ref) if q["source"] in CLASS_SOURCES else None

    if mix["loop"] == "closed":
        n = int(mix["clients"])
        slos = _split(mix["slo"], n, _rng(seed, 3))
        sems = _split(mix.get("semantics"), n, _rng(seed, 4))
        tenants = _tenants(mix, n, seed)
        if classes is not None:
            seqs = [_cycle(classes, _rng(seed, 1000 + c)) for c in range(n)]
            pool = [(query, starts, True) for query, starts in classes]
        else:
            pool = _stream(q, graph, int(q.get("n_queries", 1000)))
            seqs = [itertools.cycle(pool[c::n]) for c in range(n)]
        clients = [
            _caller(seqs[c], tenant=tenants[c], slo=slos[c], strategy=strategy, semantics=sems[c])
            for c in range(n)
        ]
        slo = next(iter(mix["slo"]))
        candidates = [
            Request(query, starts, tenants[0], slo, strategy, hot=hot, semantics=s)
            for s in dict.fromkeys(sems) for query, starts, hot in pool
        ]
        warm, prebuild = _warm(mix, candidates)
        return Traffic("closed", warm, [], clients, prebuild=prebuild)

    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = int(round(float(mix["rate_qps"]) * seconds))
    fixed = "schedule_seed" in mix
    draw = int(mix["schedule_seed"]) if fixed else seed
    rng = _rng(draw, 2)
    due = _due(mix, n, seconds, rng)
    slos = _split(mix["slo"], n, rng)
    sems = _split(mix.get("semantics"), n, _rng(draw, 4))
    tenants = _tenants(mix, n, seed, shuffled=fixed)
    if classes is not None:
        items = list(itertools.islice(_cycle(classes, _rng(draw, 1000)), n))
    else:
        items = _stream(q, graph, n)
    schedule = [
        Request(query, starts, tenants[i], slos[i], strategy, float(due[i]), hot, sems[i])
        for i, (query, starts, hot) in enumerate(items)
    ]
    warm, prebuild = _warm(mix, schedule)
    return Traffic("open", warm, schedule, prebuild=prebuild)
