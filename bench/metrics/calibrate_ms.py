"""Host milliseconds per flush spent feeding S2's observed costs to the
calibrator (one ``Calibrator.observe`` a start): the program's
``s2.calibrate`` spans summed over each flush ending in the window,
averaged over those flushes (``calibrate_ms.<cell kind>``)."""

from yardstick import program


def read(obs):
    return program.per_flush_ms(obs, "s2.calibrate")
