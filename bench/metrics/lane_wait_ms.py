"""Milliseconds an admitted request waited in its batching lane: from
its routing to a lane to the hand-over of the flush that carries it to
the flush worker, the mean of the program's ``aio.lane_wait``
intervals ending in the window (``lane_wait_ms.<cell kind>``)."""

from yardstick import program


def read(obs):
    return program.mean_ms(obs, "aio.lane_wait")
