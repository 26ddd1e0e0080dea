"""Device idle share of the traced window, in percent: 1 minus the union
of the intervals in which an operation ran on the chip, over the window
(``device_idle.<cell kind>``)."""


def read(obs):
    t = obs.trace
    if t is None or t.n_devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
