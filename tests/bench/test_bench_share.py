"""The expert-share serving runner at a size a test run holds: a sound run
is correct, the float8 control reads above the limit, ``prefill_flops``
is the chip's share of the model's work, and ``moe_local_share.prefill``
reads the share of routed pairs whose expert the chip holds."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, model, serve, serve_share, traffic
from bench.reference import _mm, _norm
from bench.reference_share import ShareReference
from bench.spans import Spans

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "tests/bench/data/tiny-share.json").read_text())
MIX = json.loads((ROOT / "tests/bench/data/tiny-serve.json").read_text())
SEED = 2**31 + 5
LIMIT = TINY["check"]["served_mismatch_share"]


def _context():
    return harness.Context(
        cell={"name": "tiny-share", "chips": 1}, config=TINY, traffic=MIX,
        seed=SEED, seconds=2.0, trace=False, t_start=time.perf_counter(),
        peak={"bf16_flops_per_s": 197e12}, devices=jax.devices()[:1])


def test_sound_run_is_correct():
    ctx = _context()
    res = serve_share.run(ctx)
    spec = {"end_to_end": [], "per_layer": []}
    line = harness.result_line(ROOT, spec, ctx.cell, ctx, res)
    assert line["correct"], line["checks"]
    assert line["checks"]["served_mismatch_share"]["limit"] == LIMIT
    assert res["attempted"] == round(MIX["arrivals"]["rate_rps"] * 2.0)
    assert res["obs"]["kind"] == "serve" and res["obs"]["prefill_flops"] > 0


def test_control_reads_above_the_limit():
    c = serve_share.Cell(TINY, MIX, SEED, Spans(trace=False))
    c.warm_up()
    reqs = traffic.serve_schedule(MIX, c.cfg.vocab_size, SEED, 2.0)
    eng, _, _ = serve.serve(c, reqs, 2.0)
    picked = serve.sample([r for r in eng.finished
                           if not r.failed and r.output], SEED)
    r = serve_share.check(TINY, SEED, picked, control=True)
    assert r["tokens_compared"] >= serve.SAMPLE_TOKENS
    assert r["served_mismatch_share"] <= LIMIT < r["control_mismatch_share"]


def test_model_holds_the_block_under_the_whole_router():
    cfg = serve_share.model_config(TINY)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.first_expert) == (
        32, 8, 8)
    assert cfg.moe.top_k == 8 and cfg.moe.score_fn == "softmax"
    assert cfg.qk_norm and cfg.num_layers == 4


def test_prefill_flops_hand_count():
    """One 40-token prompt in chunks of 32 on the tiny configuration."""
    D, q, kv = 128, 16 * 16, 1 * 16
    attn = D * q + 2 * D * kv + q * D                    # q, k and v, o
    router = D * 32                                      # the whole router
    experts = 8 * 8 // 32 * 3 * D * 64                   # top-8 x 8/32 held
    layer = 2 * (attn + router + experts)
    head = 2 * 1024 * D
    scores = 4 * 16 * 16 * sum(p + 1 for p in range(40))  # per layer
    want = 40 * 4 * layer + head + 4 * scores
    req = SimpleNamespace(rid=0, prompt=np.zeros(40, np.int32))
    got = serve_share.prefill_flops(serve_share.dims(TINY), [req], {0: 0.0},
                                    32)
    assert got == want
    # Requests that never started prefilling add nothing.
    assert serve_share.prefill_flops(serve_share.dims(TINY), [req], {},
                                     32) == 0
    # Against the whole model: the experts' part is a quarter.
    whole = flops.dims(dict(TINY, num_experts=32))
    assert flops.linear_flops(whole, head=False) - flops.linear_flops(
        serve_share.dims(TINY), head=False) == 4 * 2 * 6 * 3 * D * 64


def _host_local_share(config, tokens) -> float:
    """Share of routed pairs in the held block over every layer, from the
    reference's own forward pass of ``tokens`` (float32)."""
    ref = ShareReference(config, SEED)
    top = ref.top_weights()
    x = jnp.take(top["embedding"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    pos = jnp.arange(len(tokens))
    here = routed = 0
    for layer in range(ref.L):
        w = ref.layer_weights(layer)
        q, k, v = ref._qkv(w, _norm(x, w["norm1"]), pos, "f32")
        att = ref._attend(q, pos, k, v, len(tokens), "f32")
        x = x + _mm("sk,kd->sd", att.reshape(len(tokens), -1), w["wo"],
                    "f32")
        h = _norm(x, w["norm2"])
        _, ids = jax.lax.top_k(_mm("td,de->te", h, w["router"], "f32"),
                               config["num_experts_per_tok"])
        local = np.asarray(ids) - ref.first
        here += int(((local >= 0) & (local < ref.E)).sum())
        routed += ids.size
        x = x + ref._ffn(w, h, "f32")
    return 100.0 * here / routed


def test_local_share_reader_matches_a_host_recount(tmp_path):
    from repro import tracing
    from repro.models.transformer import ParallelCtx
    from repro.serving.adapter import make_engine_fns

    config = dict(TINY, program=dict(TINY["program"], dtype="float32"))
    cfg = serve_share.model_config(config)
    rcfg = model.runtime_config(config, balancer="ultraep", cf_pair=1.0,
                                cf_slot=10 / 8)
    pctx = ParallelCtx(mesh=None)
    params = model.make_params(SEED, cfg, rcfg, pctx)
    prefill, _, new_cache, _, _ = make_engine_fns(params, cfg, rcfg, pctx,
                                                  max_seq=64)
    tokens = np.random.default_rng(3).integers(0, 1024, 64).astype(np.int32)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tracing.reset()
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            caches = new_cache(1)
            for s in (0, 32):           # two whole chunks: no padding
                _, caches = prefill(jnp.asarray(tokens[None, s:s + 32]),
                                    caches, s, 32)
        got = harness.read_metric(ROOT, "moe_local_share.prefill",
                                  {"kind": "serve"})
    finally:
        tracing.reset()
    want = _host_local_share(config, tokens)
    assert 0 < want < 100
    assert got == pytest.approx(want, abs=1e-9)
    assert harness.read_metric(ROOT, "moe_local_share.prefill",
                               {"kind": "serve"}) is None
