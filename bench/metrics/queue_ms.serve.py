"""Median wait of a request in the engine's queue: the start of its first
prefill call minus the time it fell due (benchmark clock, ms)."""

import statistics


def read(obs):
    q = obs.get("queue_s") if obs.get("kind") == "serve" else None
    return statistics.median(q) * 1e3 if q else None
