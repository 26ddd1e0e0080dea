"""Host milliseconds per S2 executor call spent waiting for the device
and copying its outputs to the host: the mean of the program's
``s2.fetch`` spans ending in the window (``s2_fetch_ms.<cell kind>``).
Dispatch returns before the device is done, so this holds the
fixpoint's device time as seen from the host."""

from yardstick import program


def read(obs):
    return program.mean_ms(obs, "s2.fetch")
