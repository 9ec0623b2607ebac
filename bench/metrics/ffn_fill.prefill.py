"""Share of the expert FFN's slot rows that hold a routed pair of a prompt
token in prefill (%): per layer and call, the routed pairs the slots held,
at most the top-k pairs of the chunk's prompt tokens (its right-padding is
routed too), over the slot rows the FFN ran, summed over the window's
prefill calls.  Exact while nothing drops (``moe_drop_share.serve``) and
whether or not padding is routed.  The program keeps each call's counters
(``repro.tracing.count``) while the profiler records; a program without
them gives nothing."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    calls = [s for s in tracing.counts() if s.kind == "prefill"]
    rows = sum(sum(s.slot_rows) for s in calls)
    if not rows:
        return None
    import jax

    held = jax.device_get([s.counters.held for s in calls])
    used = sum(min(int(h), v) for s, hs in zip(calls, held)
               for h, v in zip(hs, s.valid_pairs))
    return 100.0 * used / rows
