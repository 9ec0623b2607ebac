"""Named scopes in the lowered steps, MoE counters out of prefill and
decode, and the program's host spans in the profiler's trace."""

import glob
import itertools
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import ModelConfig, MoEArch
from repro.core.balancer import BalancerConfig
from repro.models import transformer
from repro.models.model import decode_step, init_caches, init_lm, prefill_step
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.serving.adapter import make_engine_fns
from repro.serving.engine import EngineConfig, Request, ServingEngine

PCTX = ParallelCtx(mesh=None)
SCOPES = ("embed", "attn", "ffn.dense", "moe.gate", "moe.plan",
          "moe.distribute", "moe.dispatch", "moe.ffn", "moe.combine",
          "moe.shared", "head")


def _glm_like() -> ModelConfig:
    """GLM-4.5's layout at toy widths: GQA with qkv bias, a leading dense
    layer, sigmoid-gated experts with the aux-free bias, a shared expert."""
    return ModelConfig(
        name="tiny-glm", family="moe", num_layers=2, d_model=32,
        vocab_size=128, num_heads=4, num_kv_heads=2, head_dim=8,
        qkv_bias=True, d_ff=64,
        moe=MoEArch(num_experts=8, top_k=2, d_ff=32, score_fn="sigmoid",
                    use_bias=True, aux_loss_weight=0.0, n_shared_experts=1,
                    shared_d_ff=32, first_dense_layers=1, n_slot=2))


def _rcfg(cf_slot=8.0):
    return RuntimeConfig(balancer=BalancerConfig(mode="ultraep", n_slot=2),
                         cf_pair=8, cf_slot=cf_slot, remat=False)


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler trace; (its result, the trace's events
    as (name, start, end, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((ev.name, ev.start_ns, ev.end_ns,
                               dict(ev.stats)))
    return out, events


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_lowered_steps_carry_every_scope(step):
    cfg, rcfg = _glm_like(), _rcfg()
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, PCTX)
    batch, seq = (1, 16) if step == "prefill" else (2, 1)
    caches = init_caches(cfg, batch, 32, rcfg)
    toks = jnp.zeros((batch, seq), jnp.int32)
    fn = prefill_step if step == "prefill" else decode_step
    text = jax.jit(lambda p, c, t: fn(p, c, t, cfg, rcfg, PCTX)).lower(
        params, caches, toks).as_text(debug_info=True)
    missing = [s for s in SCOPES if f"/{s}/" not in text]
    assert not missing


def test_prefill_counters_match_the_layer_stats(monkeypatch):
    """A hand-sized prefill whose slots are too small for its pairs: the
    step's counters are the MoE layer's own stats, and its slot rows are
    the layer's slots times their capacity."""
    cfg, rcfg = _glm_like(), _rcfg(cf_slot=0.5)
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, PCTX)
    seen = []
    layer = transformer.moe_layer_local

    def spy(x, mp, mcfg, **kw):
        out = layer(x, mp, mcfg, **kw)
        seen.append((mcfg, out[2]))
        return out

    monkeypatch.setattr(transformer, "moe_layer_local", spy)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    _, _, c = prefill_step(params, init_caches(cfg, 1, 32, rcfg), toks, cfg,
                           rcfg, PCTX, valid_len=16)
    ((mcfg, stats),) = seen
    drops = int(stats.drops_dispatch + stats.drops_slot)
    assert drops > 0
    np.testing.assert_array_equal(c.drops, [0, drops])
    np.testing.assert_array_equal(
        c.held, [0, int(stats.counts.sum()) - drops])
    assert int(c.held[1] + c.drops[1]) == 16 * cfg.moe.top_k
    rows = transformer.moe_slot_rows(cfg, rcfg, PCTX, 1, 16)
    assert rows == (mcfg.layout.experts_per_rank + mcfg.layout.n_slot) \
        * mcfg.cap_slot
    assert int(stats.max_slot_load) <= mcfg.cap_slot


def test_span_off_is_one_shared_object():
    assert not tracing.active()
    tracing.reset()
    a = tracing.span("engine.prefill", rid=3)
    assert a is tracing.span("engine.decode", n=4) is tracing.span("x")
    tracing.count("prefill", object(), (0, 1), (0, 0))
    assert tracing.counts() == []
    tracemalloc.start()
    try:
        calls = itertools.repeat(None, 1000)
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        for _ in calls:
            tracing.span("engine.decode", n=4)
        assert tracemalloc.get_traced_memory()[1] == before
    finally:
        tracemalloc.stop()


def test_span_on_nests_and_carries_its_ids(tmp_path):
    def body():
        assert tracing.active()
        with tracing.span("engine.prefill", rid=7):
            with tracing.span("engine.prefill_chunk", rid=7, pos=512):
                tracing.count("prefill", "counters", (0, 5), (0, 2))
        return tracing.counts()

    kept, events = _profiled(tmp_path, body)
    tracing.reset()
    assert kept == [("prefill", "counters", (0, 5), (0, 2))]
    assert not tracing.active()
    spans = {n: (s, e, st) for n, s, e, st in events
             if n.startswith(tracing.PREFIX)}
    outer, inner = spans["uep.engine.prefill"], spans[
        "uep.engine.prefill_chunk"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    assert outer[2] == {"rid": 7}
    assert inner[2] == {"rid": 7, "pos": 512}


def test_engine_same_outputs_traced_and_not(tmp_path):
    cfg, rcfg = _glm_like(), _rcfg()
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, PCTX)
    fns = make_engine_fns(params, cfg, rcfg, PCTX, max_seq=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (20, 9, 33)]

    def serve():
        prefill, decode, new_cache, stack, unstack = fns
        eng = ServingEngine(EngineConfig(chunk_size=16, decode_batch=2,
                                         max_seq=64),
                            prefill_fn=prefill, decode_fn=decode,
                            new_cache_fn=new_cache, stack_caches=stack,
                            unstack_caches=unstack)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
        return {r.rid: r.output for r in eng.run()}

    plain = serve()
    assert tracing.counts() == []
    traced, events = _profiled(tmp_path, serve)
    kept = tracing.counts()
    tracing.reset()
    assert traced == plain

    names = [(n, st) for n, _, _, st in events
             if n.startswith(tracing.PREFIX)]
    assert sorted(st["rid"] for n, st in names
                  if n == "uep.engine.prefill") == [0, 1, 2]
    chunks = sorted((st["rid"], st["pos"]) for n, st in names
                    if n == "uep.engine.prefill_chunk")
    assert chunks == [(0, 0), (0, 16), (1, 0), (2, 0), (2, 16), (2, 32)]
    for name in ("schedule", "new_cache", "sample", "decode",
                 "stack_caches", "unstack_caches"):
        assert any(n == "uep.engine." + name for n, _ in names), name
    assert {st["n"] for n, st in names if n == "uep.engine.decode"} <= {1, 2}

    # One entry per call: every routed pair of the MoE layer, padding
    # included, is held or dropped, the dense layer routes none, and the
    # pairs of a chunk's valid tokens are counted apart (prompts of 20, 9
    # and 33 tokens).
    k = cfg.moe.top_k
    rows = transformer.moe_slot_rows(cfg, rcfg, PCTX, 1, 16)
    pre = [s for s in kept if s.kind == "prefill"]
    dec = [s for s in kept if s.kind == "decode"]
    assert len(pre) == len(chunks) and dec
    assert sorted(s.valid_pairs for s in pre) == [
        (0, 1 * k), (0, 4 * k), (0, 9 * k)] + [(0, 16 * k)] * 3
    for s in pre:
        assert s.slot_rows == (0, rows)
        np.testing.assert_array_equal(
            np.asarray(s.counters.held + s.counters.drops), [0, 16 * k])
    for s in dec:
        assert s.slot_rows[0] == 0 and s.slot_rows[1] > 0
        assert s.valid_pairs == (0, 2 * k)
        np.testing.assert_array_equal(
            np.asarray(s.counters.held + s.counters.drops), [0, 2 * k])
