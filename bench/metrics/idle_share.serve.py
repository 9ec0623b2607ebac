"""Share of the window in which no operation ran on the chip (%), from
the profiler's trace: 1 - union of device op intervals / window."""


def read(obs):
    tr = obs.get("trace")
    if obs.get("kind") != "serve" or not tr:
        return None
    dev = tr["devices"][tr["busiest"]]
    return 100.0 * (1.0 - dev["busy_s"] / tr["window_s"])
