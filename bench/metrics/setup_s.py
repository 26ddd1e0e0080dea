"""Seconds from the process's start to the end of warm-up: the
deployment built, every shape the traffic uses planned, compiled (or
read from the cache) and run once."""


def read(obs):
    return obs.setup_s
