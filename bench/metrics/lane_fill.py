"""Lane fill: the share of each flushed batching lane's fill target that
its starts filled, over the window (``AsyncQueryService.aio_stats()``,
``batch_window.fill_ratio``), in percent (``lane_fill.<cell kind>``)."""


def read(obs):
    window = obs.aio_stats["batch_window"]
    if not window["lanes_flushed"]:
        return None
    return 100.0 * window["fill_ratio"]
