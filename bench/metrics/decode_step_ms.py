"""Median wall time of one decode call, device-synced: the benchmark's
span around the engine's decode function (ms)."""

import statistics


def read(obs):
    d = obs.get("decode_s")
    return statistics.median(d) * 1e3 if d else None
