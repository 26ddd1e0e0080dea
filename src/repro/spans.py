"""Spans and counters at the serving path's layer boundaries.

One recorder for the whole process.  A layer opens a span around its
work::

    with spans.span("s2.fetch") as sp:
        rows = np.asarray(acc)
        sp.count("answer_bytes", rows.nbytes)

**Off** (the default) :func:`span` returns the shared :data:`NO_SPAN`: no
clock read, no allocation, no annotation; its ``count`` does nothing and
it is falsy, so work done only to describe a span (a list of ticket
ids, a device value read back) sits behind ``if sp:``.

**On** (:func:`enable`) each span is a :class:`Record` with its name,
``t0``/``t1`` on :func:`time.perf_counter`, its own id, the id of the
span open around it on the same thread (``parent``), the request (a
:class:`~repro.serve.service.Ticket`'s ``id``) and the flush it belongs
to, and its counters.  ``request`` and ``flush`` pass from a span to the
spans opened inside it unless those name their own.  The same ``with``
block opens a ``jax.profiler.TraceAnnotation("rpq.<name>")``, so the
profiler's trace carries every span at the same boundaries and a gap in
the device's work can be put down to the innermost span the host was
in.  A ``TraceAnnotation`` belongs to its thread: never hold a span open
across an ``await``; a wait that spans event-loop turns is recorded
after the fact with :func:`interval`, which writes no annotation.

Records stay in memory until :func:`drain` returns and clears them.
Spans go per flush, per request, per group and per executor call,
never per start.
"""

from __future__ import annotations

import itertools
import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "rpq."  # the spans' annotation names in the profiler's trace


class Record:
    """One span or interval.  ``counters`` maps a counter's key to its
    value (numbers, or a label such as an SLO class); ``attrs`` holds
    what describes the span without being counted (the ticket ids a
    flush carries)."""

    __slots__ = ("name", "t0", "t1", "id", "parent", "request", "flush",
                 "counters", "attrs", "_ann", "_recorder")

    def __init__(self, name: str, id: int, request: int | None, flush: int | None,
                 recorder: "Recorder | None" = None):
        self.name = name
        self.id = id
        self.request = request
        self.flush = flush
        self.parent: int | None = None
        self.t0 = self.t1 = 0.0
        self.counters: dict = {}
        self.attrs: dict = {}
        self._recorder = recorder  # the recorder a span's `with` block writes to
        self._ann = None

    def count(self, key: str, value=1) -> None:
        """Add ``value`` to the counter ``key`` (a label replaces it)."""
        if isinstance(value, str):
            self.counters[key] = value
        else:
            self.counters[key] = self.counters.get(key, 0) + value

    def __enter__(self) -> "Record":
        stack = self._recorder._stack()
        if stack:
            up = stack[-1]
            self.parent = up.id
            if self.request is None:
                self.request = up.request
            if self.flush is None:
                self.flush = up.flush
        stack.append(self)
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        self._recorder._stack().pop()
        self._recorder._records.append(self)
        return False

    def __repr__(self) -> str:
        return (f"Record({self.name!r}, id={self.id}, parent={self.parent}, "
                f"request={self.request}, flush={self.flush}, "
                f"ms={1e3 * (self.t1 - self.t0):.3f}, counters={self.counters})")


class _NoSpan:
    """What :func:`span` returns while recording is off."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False

    def count(self, key: str, value=1) -> None:
        pass


NO_SPAN = _NoSpan()


class Recorder:
    """The process's span records and the switch that turns them on."""

    def __init__(self):
        self.on = False
        self._records: list[Record] = []
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list[Record]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def drain(self) -> list[Record]:
        # other threads may append meanwhile: take and delete the first
        # n records (each step atomic on one list) so none is lost
        n = len(self._records)
        out = self._records[:n]
        del self._records[:n]
        return out


RECORDER = Recorder()


def span(name: str, request: int | None = None, flush: int | None = None):
    """A context manager around one layer's work: a :class:`Record` while
    recording is on, else :data:`NO_SPAN`."""
    if not RECORDER.on:
        return NO_SPAN
    return Record(name, next(RECORDER._ids), request, flush, RECORDER)


def interval(name: str, t0: float, t1: float, request: int | None = None,
             flush: int | None = None, **counters) -> None:
    """Record a wait from ``t0`` to ``t1`` (``time.perf_counter``) that
    crosses event-loop turns; it has no parent and no annotation."""
    if RECORDER.on:
        rec = Record(name, next(RECORDER._ids), request, flush)
        rec.t0, rec.t1 = t0, t1
        rec.counters.update(counters)
        RECORDER._records.append(rec)


def recording() -> bool:
    return RECORDER.on


def enable() -> None:
    RECORDER.on = True


def disable() -> None:
    RECORDER.on = False


def drain() -> list[Record]:
    """Return the finished records, oldest first, and forget them."""
    return RECORDER.drain()
