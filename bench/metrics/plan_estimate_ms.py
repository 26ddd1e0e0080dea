"""Milliseconds of the §5 rollout estimation per plan-cache miss: the
mean of the program's ``plan.estimate`` spans ending in the window
(``plan_estimate_ms.<cell kind>``); the part of ``plan_ms`` that the
rollouts take."""

from yardstick import program


def read(obs):
    return program.mean_ms(obs, "plan.estimate")
