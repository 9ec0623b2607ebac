"""Share of the routed (token, expert) pairs that the MoE layers dropped
at pair or slot capacity (%), over the window's prefill and decode calls:
Σ drops over Σ (held + drops), from the counters the program keeps
(``repro.tracing.count``) while the profiler records.  The cell sizes its
slots so that nothing drops; a reading above 0 is a departure from the
outputs of dropless routing.  A program without the counters gives
nothing."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    steps = tracing.counts()
    if not steps:
        return None
    import jax

    got = jax.device_get([(s.counters.held, s.counters.drops)
                          for s in steps])
    drops = sum(int(d.sum()) for _, d in got)
    routed = drops + sum(int(h.sum()) for h, _ in got)
    return 100.0 * drops / routed if routed else None
