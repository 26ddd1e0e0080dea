"""Host milliseconds per S2 executor call: the mean length of the
``strategies.s2_execute`` calls in the window (the fused fixpoint and
its meters on the device, the answer rows' copy to the host, one
observed cost per start): the part of a flush that the executor sets
(``s2_exec_ms.<cell kind>``)."""

SPANS = [{"name": "s2_execute", "on": "repro.core.strategies", "call": "s2_execute"}]


def read(obs):
    ms = obs.spans.durations_ms("s2_execute")
    return sum(ms) / len(ms) if ms else None
