"""95th percentile of the latency, in ms, from the time a request was due
to its answer, over every request due in the window."""

import numpy as np


def read(obs):
    lat = obs.latencies_ms()
    return float(np.percentile(lat, 95)) if lat else None
