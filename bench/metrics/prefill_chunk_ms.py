"""Median wall time of one prefill call (one chunk), device-synced:
the benchmark's span around the engine's prefill function (ms)."""

import statistics


def read(obs):
    d = obs.get("prefill_s")
    return statistics.median(d) * 1e3 if d else None
