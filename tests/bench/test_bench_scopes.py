"""The reduction by the program's own names: device time per named scope
over leaf ops, idle gaps named by the innermost ``uep.`` span, chip-idle
stretches inside decode calls with the runtime's host events over them.
On a made-up trace whose answers are known by hand, and on a profile
taken here on the CPU."""

import gzip
import json
import time
from pathlib import Path

import jax
import pytest

from bench import scopes
from bench.trace import Event

ROOT = Path(__file__).resolve().parents[2]

H, D = "/host:CPU", "/device:TPU:0"
OPS, MODS = "XLA Ops", "XLA Modules"
PRE, DEC = "jit__prefill(11)", "jit__decode(22)"


def _op(name, s, e):
    return Event(D, OPS, f"%{name} = bf16[8]{{0}} {name.split('.')[0]}(x)",
                 s, e)


def _made_up() -> scopes.Profile:
    ev = [Event(H, "python", "bench.window", 0.0, 10.0),
          Event(H, "python", "bench.wait_arrivals", 9.0, 10.0),
          Event(H, "python", "uep.engine.schedule", 0.5, 9.0),
          Event(H, "python", "uep.engine.prefill", 0.8, 3.5),
          Event(H, "python", "uep.engine.prefill_chunk", 0.9, 3.1),
          Event(H, "python", "uep.engine.sample", 3.5, 3.7),
          Event(H, "python", "uep.engine.decode", 4.2, 7.0),
          Event(H, "main/1", "CommonPjRtLoadedExecutable::Execute", 6.2,
                6.8),
          Event(H, "main/1", "ReadSyncFlag", 1.5, 1.6),
          Event(D, MODS, PRE, 1.0, 3.0),
          Event(D, MODS, DEC, 5.0, 6.0),
          _op("fusion.1", 1.0, 1.2),
          _op("while.3", 1.2, 2.2),      # its body's ops are events too
          _op("fusion.7", 1.3, 1.8),
          _op("copy.2", 1.8, 2.1),
          _op("fusion.9", 2.2, 2.8),
          _op("fusion.10", 2.8, 3.0),    # no path: no scope
          _op("fusion.1", 5.0, 5.5),     # same op name, other program
          _op("fusion.4", 5.5, 6.0)]
    paths = {(PRE, "fusion.1"): "jit(_prefill)/jit(main)/embed/gather",
             (PRE, "while.3"): "jit(_prefill)/jit(main)/attn/while",
             (PRE, "fusion.7"):
             "jit(_prefill)/jit(main)/attn/while/body/dot_general",
             (PRE, "copy.2"): "jit(_prefill)/jit(main)/attn/while",
             (PRE, "fusion.9"): "jit(_prefill)/jit(main)/moe.ffn/dot",
             (DEC, "fusion.1"): "jit(_decode)/jit(main)/moe.distribute/rs",
             (DEC, "fusion.4"): "jit(_decode)/jit(main)/attn/pad"}
    return scopes.Profile(ev, paths)


def test_leaf_ops_leave_out_a_while_over_its_body():
    ops = [e for e in _made_up().events if e.line == OPS]
    leaves = {e.name.split()[0] for e in scopes.leaf_ops(ops)}
    assert "%while.3" not in leaves
    assert {"%fusion.7", "%copy.2", "%fusion.9"} <= leaves
    assert len(leaves) == 6


def test_scope_seconds_and_runs():
    r = scopes.reduce_scopes(_made_up())
    pre, dec = r["scope_s"]["jit__prefill"], r["scope_s"]["jit__decode"]
    assert pre == pytest.approx({"embed": 0.2, "attn": 0.8, "moe.ffn": 0.6,
                                 scopes.NO_SCOPE: 0.2})
    assert dec == pytest.approx({"moe.distribute": 0.5, "attn": 0.5})
    assert r["runs"] == {"jit__prefill": 1, "jit__decode": 1}
    assert scopes.scope_of("a/moe.gate/b/attn/c") == "attn"
    assert scopes.scope_of("jit(f)/x") == scopes.NO_SCOPE


def test_idle_named_by_the_innermost_program_span():
    r = scopes.reduce_scopes(_made_up())
    # Idle 0-1, 3-5 and 6-10 (7 s), cut where the uep spans open and close.
    assert r["named_idle_s"] == pytest.approx({
        scopes.NO_SPAN: 1.5, "uep.engine.schedule": 2.8,
        "uep.engine.prefill": 0.5, "uep.engine.prefill_chunk": 0.2,
        "uep.engine.sample": 0.2, "uep.engine.decode": 1.8})


def test_decode_stalls_and_the_runtime_events_over_them():
    r = scopes.reduce_scopes(_made_up())
    assert r["decode_idle_s"] == pytest.approx(1.8)
    (at1, d1, host1), (at2, d2, host2) = r["decode_stalls"]
    assert (at1, d1) == pytest.approx((6.0, 1.0))
    assert (at2, d2) == pytest.approx((4.2, 0.8))
    assert host1 == [["main/1", "CommonPjRtLoadedExecutable::Execute",
                      pytest.approx(0.2), pytest.approx(0.6)]]
    assert host2 == []


def test_metrics_from_the_made_up_trace():
    m = scopes.metrics(scopes.reduce_scopes(_made_up()))
    assert m == pytest.approx({
        "attn_ms.prefill": 800.0, "attn_ms.decode": 500.0,
        "distribute_ms.decode": 500.0, "expert_ffn_ms.prefill": 600.0,
        "idle_engine_share.serve": 55.0, "decode_idle_ms.serve": 1800.0})
    assert set(m) <= set(scopes.UNITS)


def test_a_trace_without_program_spans_or_scopes_reads_nothing():
    p = _made_up()
    bare = scopes.Profile([e for e in p.events
                           if not e.name.startswith(scopes.UEP)], {})
    assert scopes.metrics(scopes.reduce_scopes(bare)) == {}


def test_op_paths_from_the_trace_json(tmp_path):
    meta = [{"ph": "M", "pid": 3, "tid": t, "name": "thread_name",
             "args": {"name": n}} for t, n in ((2, MODS), (3, OPS))]
    x = [{"ph": "X", "pid": 3, "tid": 2, "ts": 10.0, "dur": 5.0,
          "name": PRE},
         {"ph": "X", "pid": 3, "tid": 2, "ts": 20.0, "dur": 5.0,
          "name": DEC},
         {"ph": "X", "pid": 3, "tid": 3, "ts": 11.0, "dur": 1.0,
          "name": "fusion.1", "args": {
              "long_name": "%fusion.1 = f32[2]{0} fusion(a)",
              "tf_op": "jit(_prefill)/jit(main)/embed/gather:"}},
         {"ph": "X", "pid": 3, "tid": 3, "ts": 21.0, "dur": 1.0,
          "name": "fusion.1", "args": {
              "long_name": "%fusion.1 = f32[2]{0} fusion(b)",
              "tf_op": "jit(_decode)/jit(main)/attn/pad:"}},
         {"ph": "X", "pid": 3, "tid": 3, "ts": 22.0, "dur": 1.0,
          "name": "copy-start", "args": {"long_name": "%copy-start = x"}}]
    path = tmp_path / "t.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": meta + x}, f)
    assert scopes.op_paths(str(path)) == {
        (PRE, "fusion.1"): "jit(_prefill)/jit(main)/embed/gather",
        (DEC, "fusion.1"): "jit(_decode)/jit(main)/attn/pad"}


def test_load_profile_keeps_program_and_bench_spans(tmp_path):
    """A profile taken here: its host spans on one clock, nested."""
    from repro import tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            with tracing.span("engine.decode", n=2):
                time.sleep(0.002)
    ev = scopes.load_profile(str(tmp_path)).events
    (win,) = [e for e in ev if e.name == "bench.window"]
    (dec,) = [e for e in ev if e.name == "uep.engine.decode"]
    assert win.start <= dec.start < dec.end <= win.end
    assert dec.end - dec.start >= 0.002


def _tiny_prefills(tmp_path, valid):
    """The tiny cell's prefill run once per entry of ``valid`` (valid
    tokens of a chunk; None: all) under a profiler trace; (chunk, top-k,
    slots)."""
    import jax.numpy as jnp

    from bench import serve
    from bench.spans import Spans

    tiny = json.loads((ROOT / "tests/bench/data/tiny-moe.json").read_text())
    mix = json.loads((ROOT / "tests/bench/data/tiny-serve.json").read_text())
    chunk = mix["engine"]["chunk"]
    prefill, _, new_cache, _, _ = serve.Cell(tiny, mix, 3, Spans(False)).fns
    toks = jnp.ones((1, chunk), jnp.int32)
    prefill(toks, new_cache(1), 0, chunk)
    with jax.profiler.trace(str(tmp_path)):
        for n in valid:
            prefill(toks, new_cache(1), 0, n or chunk)
    slots = tiny["n_routed_experts"] + tiny["program"]["n_slot"]
    return chunk, tiny["num_experts_per_tok"], slots


def test_ffn_fill_reads_the_program_counters(tmp_path):
    """Two prefill calls of the tiny cell, one chunk full and one ragged:
    each routes every token of its padded chunk, with nothing dropped, into
    32 + 2 slots of one chunk's rows; the pairs of the ragged chunk's 5
    prompt tokens count, those of its padding do not."""
    from bench import harness
    from repro import tracing

    obs = {"kind": "serve"}
    assert harness.read_metric(ROOT, "ffn_fill.prefill", obs) is None
    try:
        chunk, k, slots = _tiny_prefills(tmp_path, (None, 5))
        v = harness.read_metric(ROOT, "ffn_fill.prefill", obs)
    finally:
        tracing.reset()
    assert v == pytest.approx(100.0 * (chunk + 5) * k / (2 * slots * chunk))


@pytest.mark.parametrize("cf_slot, dropped", [(None, False), (0.25, True)])
def test_moe_drop_share_reads_the_program_counters(tmp_path, monkeypatch,
                                                   cf_slot, dropped):
    """The tiny cell's dropless slots drop nothing; slots cut to a quarter
    of the average load drop a share of the routed pairs."""
    from bench import harness, model
    from repro import tracing

    obs = {"kind": "serve"}
    assert harness.read_metric(ROOT, "moe_drop_share.serve", obs) is None
    if cf_slot is not None:
        rcfg = model.runtime_config
        monkeypatch.setattr(model, "runtime_config", lambda c, **kw: rcfg(
            c, **{**kw, "cf_slot": cf_slot}))
    try:
        _tiny_prefills(tmp_path, (None, 5))
        v = harness.read_metric(ROOT, "moe_drop_share.serve", obs)
    finally:
        tracing.reset()
    assert (0.0 < v < 100.0) if dropped else v == 0.0


def test_main_goes_through_the_harness_runner(monkeypatch, capsys):
    """``main`` is ``bench/run.py --trace 1`` with the scoped context; on
    a host without a TPU the runner refuses, and nothing is reduced."""
    from bench import harness

    monkeypatch.setattr(harness, "Context", harness.Context)
    monkeypatch.setattr(scopes.ScopedContext, "last", None)
    rc = scopes.main(["--workload", "glm-4.5-air.serve.prefill_heavy",
                      "--seed", "1", "--seconds", "1"])
    err = capsys.readouterr().err
    assert rc == 2 and "needs 1 TPU" in err and "scopes:" not in err
    assert harness.Context is scopes.ScopedContext
