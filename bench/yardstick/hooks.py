"""Spans and counters around the program's layers, taken from outside.

In a traced run the harness wraps the calls into the program's layers
that the cell's metric readers declare (each reader's ``SPANS``), on the
service instance or on the module whose attribute the program looks up
at call time, and restores them afterwards.  Each wrapper keeps (start,
end) on the host clock, and writes a ``jax.profiler`` ``TraceAnnotation``
named ``bench.<span>``, so that the trace reduction can say what the host
was doing while the device was idle.

A span is declared as a dict:

``name``
    The span's name (``bench.<name>`` in the trace).
``on``
    ``"service"`` for the cell's ``QueryService`` instance, or the dotted
    name of a module of the program (``"repro.core.strategies"``).
``call``
    The attribute wrapped.
``count``
    Optional ``{key: dotted path from the service}``: each span's info
    holds each counter's change over the call.  A step of the path is an
    attribute, a method called with no arguments where it ends in ``()``,
    or a key of a dict (``{"misses": "plan_cache.stats().misses"}``).
"""

from __future__ import annotations

import functools
import importlib
import time
from collections import defaultdict
from typing import Callable

import jax


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


class Spans:
    """Host spans by name: ``records[name]`` is a list of (t0, t1, info)."""

    def __init__(self):
        self.records: dict[str, list[tuple[float, float, dict]]] = defaultdict(list)
        self._undo: list[Callable[[], None]] = []

    def wrap(self, owner, attr: str, name: str,
             before: Callable[[], object] | None = None,
             after: Callable[[object], dict] | None = None) -> None:
        """Replace ``owner.attr`` by a timed, annotated call of itself.
        ``before()`` runs ahead of the call and ``after(token)`` turns its
        result into the span's info (for counters read around the call)."""
        fn = getattr(owner, attr)
        had_own = attr in vars(owner)
        label = f"bench.{name}"

        def timed(*args, **kwargs):
            token = before() if before else None
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(label):
                    return fn(*args, **kwargs)
            finally:
                t1 = time.perf_counter()
                self.records[name].append((t0, t1, after(token) if after else {}))

        setattr(owner, attr, timed)
        self._undo.append(
            (lambda: setattr(owner, attr, fn)) if had_own else (lambda: delattr(owner, attr))
        )

    def restore(self) -> None:
        while self._undo:
            self._undo.pop()()

    def durations_ms(self, name: str, where: Callable[[dict], bool] | None = None) -> list[float]:
        return [
            (t1 - t0) * 1e3
            for t0, t1, info in self.records.get(name, [])
            if where is None or where(info)
        ]


def declared(readers) -> list[dict]:
    """The union of the spans the readers declare, by name; two readers
    that declare one name differently are an error."""
    spans: dict[str, dict] = {}
    for reader in readers:
        for spec in getattr(reader, "SPANS", ()):
            if spans.setdefault(spec["name"], spec) != spec:
                raise ValueError(f"span {spec['name']!r} declared twice, differently")
    return list(spans.values())


def _step(obj, part: str):
    if isinstance(obj, dict):
        return obj[part]
    if part.endswith("()"):
        return getattr(obj, part[:-2])()
    return getattr(obj, part)


def _counters(service, paths: dict[str, str]) -> dict[str, float]:
    return {key: functools.reduce(_step, path.split("."), service) for key, path in paths.items()}


def install(spans: Spans, service, specs: list[dict]) -> None:
    """Wrap each declared call (see the module's docstring)."""
    for spec in specs:
        owner = service if spec["on"] == "service" else importlib.import_module(spec["on"])
        before = after = None
        if spec.get("count"):
            before = functools.partial(_counters, service, spec["count"])

            def after(c0, before=before):
                c1 = before()
                return {k: c1[k] - v for k, v in c0.items()}

        spans.wrap(owner, spec["call"], spec["name"], before=before, after=after)
