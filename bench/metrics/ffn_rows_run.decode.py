"""Share of the expert FFN's capacity rows whose products it computed in
decode (%): Σ rows run over Σ slot rows, per layer and call, over the
window's decode calls.  Rows run are the program's counter
(``MoECounters.rows_run``, kept by ``repro.tracing.count`` while the
profiler records): the filled row tiles of each slot, the kernel's tile
padding included, or every row where the FFN runs densely.  Slot rows are
the capacity rows of the slot buffers (``transformer.moe_slot_rows``).
A program without the counter gives nothing."""


def read(obs):
    if obs.get("kind") != "serve":
        return None
    try:
        from repro import tracing
    except ImportError:
        return None
    calls = [s for s in tracing.counts() if s.kind == "decode"]
    if not calls or not hasattr(calls[0].counters, "rows_run"):
        return None
    rows = sum(sum(s.slot_rows) for s in calls)
    if not rows:
        return None
    import jax

    run = jax.device_get([s.counters.rows_run for s in calls])
    return 100.0 * sum(int(r.sum()) for r in run) / rows
