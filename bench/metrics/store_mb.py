"""Megabytes (10^6 B) of the tile store the S2 level kernel reads, as
the device allocated it: the mean ``store_bytes`` counter of the
program's ``s2.fetch`` spans ending in the window (``store_mb.<cell
kind>``).  A program whose spans carry no such counter reads nothing."""

from yardstick import program


def read(obs):
    fetches = program.records(obs, "s2.fetch")
    if not fetches:
        return None
    held = [r.counters["store_bytes"] for r in fetches if "store_bytes" in r.counters]
    if not held:
        return None
    return sum(held) / len(held) / 1e6
