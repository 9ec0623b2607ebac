"""Share of the routed (token, expert) pairs of prefill whose expert this
chip holds (%): Σ (held + drops) over Σ (held + drops + absent), per layer
and call, over the window's prefill calls, from the counters the program
keeps (``repro.tracing.count``) while the profiler records.  ``absent``
counts the pairs an expert share routes to experts held on other chips;
a chip that holds all of the router's experts reads 100.  A program
without the ``absent`` counter gives nothing."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    calls = [s for s in tracing.counts() if s.kind == "prefill"]
    if not calls or not hasattr(calls[0].counters, "absent"):
        return None
    import jax

    got = jax.device_get([(s.counters.held, s.counters.drops,
                           s.counters.absent) for s in calls])
    here = sum(int(h.sum()) + int(d.sum()) for h, d, _ in got)
    routed = here + sum(int(a.sum()) for _, _, a in got)
    return 100.0 * here / routed if routed else None
