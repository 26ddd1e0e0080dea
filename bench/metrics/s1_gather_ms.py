"""Host milliseconds per S1 retrieval: the mean length of the
``strategies.s1_collect`` calls in the window (broadcast, per-site
compaction, gather of every site's matching edges to the host, dedup).
Nothing to read where the planner chose no S1."""

SPANS = [{"name": "s1_collect", "on": "repro.core.strategies", "call": "s1_collect"}]


def read(obs):
    ms = obs.spans.durations_ms("s1_collect")
    return sum(ms) / len(ms) if ms else None
