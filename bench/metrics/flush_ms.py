"""Host milliseconds per ``QueryService.flush`` call in the window: batch,
execute and gather answers (``flush_ms.<cell kind>``)."""

SPANS = [{"name": "flush", "on": "service", "call": "flush"}]


def read(obs):
    ms = obs.spans.durations_ms("flush")
    return sum(ms) / len(ms) if ms else None
