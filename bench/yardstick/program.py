"""The program's own span records (``repro.spans``), for the metric readers.

A traced run loads its per-layer readers before it builds the
deployment, and an untraced run loads only its end-to-end ones, so a
reader that imports this module turns the program's recorder on for a
traced run and for no other.  The recorder is then on from set-up to
the end of the run; :func:`records` drains it once, turns it off, keeps
the records on the run's ``Observations`` (``obs.program``) and reads
those whose interval ends inside the window.  Where the program has no
recorder (a commit from before it), every reader here reads nothing.
"""

from __future__ import annotations

try:
    from repro import spans
except ImportError:  # a program without the recorder
    spans = None
else:
    spans.enable()


def _all(obs) -> list | None:
    if spans is None:
        return None
    program = getattr(obs, "program", None)
    if program is None:
        spans.disable()
        program = obs.program = spans.drain()
    return program


def records(obs, name: str) -> list | None:
    """The records named ``name`` whose interval ends inside the window
    (``None`` where the program records no spans)."""
    program = _all(obs)
    if program is None:
        return None
    return [r for r in program if r.name == name and obs.t_open <= r.t1 <= obs.t_close]


def mean_ms(obs, name: str) -> float | None:
    """Mean length, in ms, of the ``name`` spans ending in the window."""
    recs = records(obs, name)
    if not recs:
        return None
    return 1e3 * sum(r.t1 - r.t0 for r in recs) / len(recs)


def per_flush_ms(obs, name: str) -> float | None:
    """Milliseconds of ``name`` spans per ``flush`` ending in the window:
    each such flush's ``name`` spans summed (they share its flush id),
    averaged over the flushes; ``None`` where none of them has one."""
    flushes = records(obs, "flush")
    if not flushes:
        return None
    ids = {f.flush for f in flushes}
    parts = [r for r in _all(obs) if r.name == name and r.flush in ids]
    if not parts:
        return None
    return 1e3 * sum(r.t1 - r.t0 for r in parts) / len(flushes)
