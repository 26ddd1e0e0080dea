"""The Alibaba statistical twin (arXiv:1510.04347 §4.1), from the
benchmark's copy of the program's generator; a configuration names it
as ``"graph": {"generator": "alibaba_like", ...}`` with its parameters."""

from yardstick import twin


def generate(seed: int, **params) -> twin.Graph:
    return twin.alibaba_like(seed=seed, **params)
