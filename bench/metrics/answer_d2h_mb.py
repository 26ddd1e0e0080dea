"""Megabytes (10^6 B) of S2 answers copied from the device to the host
per S2 request answered: the ``answer_bytes`` counters of the
program's ``s2.fetch`` spans ending in the window, over the
``s2.answers`` spans (one a request) ending in it
(``answer_d2h_mb.<cell kind>``)."""

from yardstick import program


def read(obs):
    fetches = program.records(obs, "s2.fetch")
    answered = program.records(obs, "s2.answers")
    if not fetches or not answered:
        return None
    return sum(r.counters.get("answer_bytes", 0) for r in fetches) / 1e6 / len(answered)
