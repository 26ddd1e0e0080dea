"""Host milliseconds per flush spent turning S2 answer rows into each
start's answer set: the program's ``s2.answers`` spans (one a request)
summed over each flush ending in the window, averaged over those
flushes (``answer_sets_ms.<cell kind>``)."""

from yardstick import program


def read(obs):
    return program.per_flush_ms(obs, "s2.answers")
