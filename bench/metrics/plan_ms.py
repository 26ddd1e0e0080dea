"""Host milliseconds per plan-cache miss: the mean length of the
``QueryService.plan_request`` calls in the window during which the
service's ``PlanCache`` counted a miss (a §5 rollout estimation on the
event loop)."""

SPANS = [{"name": "plan_request", "on": "service", "call": "plan_request",
          "count": {"misses": "plan_cache.stats().misses"}}]


def read(obs):
    ms = obs.spans.durations_ms("plan_request", where=lambda info: info["misses"] > 0)
    return sum(ms) / len(ms) if ms else None
