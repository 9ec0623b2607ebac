"""Per-arch smoke tests: REDUCED config of the same family, one forward +
train step on CPU, asserting output shapes and no NaNs (brief requirement).
Full configs are exercised only via the dry-run (ShapeDtypeStruct)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.configs.base import layer_kinds
from repro.configs.reduce import reduced
from repro.core.balancer import BalancerConfig
from repro.launch.specs import supported_shapes
from repro.models.model import (
    decode_step,
    forward,
    init_caches,
    init_lm,
    init_router_bias,
    lm_loss,
)
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.optim import adamw
from repro.train.loop import TrainConfig, init_train_state, make_train_step

B, S = 2, 32
PCTX = ParallelCtx(mesh=None)


def _batch(cfg, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    b = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)}
    if cfg.frontend == "audio_frames":
        b["frames"] = jax.random.normal(ks[2], (B, S, cfg.d_model))
    if cfg.frontend == "vision_patches":
        b["patches"] = jax.random.normal(ks[2], (B, cfg.num_patches,
                                                 cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_forward_and_train_step(arch):
    cfg = reduced(get_config(arch))
    rcfg = RuntimeConfig(balancer=BalancerConfig(
        mode="ultraep", n_slot=cfg.moe.n_slot if cfg.moe else 2),
        cf_pair=8, cf_slot=8)
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, PCTX)
    bias = init_router_bias(cfg)
    batch = _batch(cfg)
    logits, aux, drops, counts = jax.jit(
        lambda p, b: forward(p, b, cfg, rcfg, PCTX, router_bias=bias)
    )(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    loss = lm_loss(logits, batch["targets"])
    assert np.isfinite(float(loss))

    opt = adamw(1e-3)
    state = init_train_state(params, opt, cfg)
    step = jax.jit(make_train_step(cfg, rcfg, PCTX, opt, TrainConfig()))
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"])), arch


@pytest.mark.parametrize("arch", [a for a in ASSIGNED_ARCHS
                                  if get_config(a).has_decode])
def test_reduced_decode_step(arch):
    cfg = reduced(get_config(arch))
    rcfg = RuntimeConfig(balancer=BalancerConfig(
        mode="ultraep", n_slot=cfg.moe.n_slot if cfg.moe else 2),
        cf_pair=8, cf_slot=8)
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, PCTX)
    caches = init_caches(cfg, B, 16, rcfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0,
                              cfg.vocab_size)
    logits, caches, counters = jax.jit(
        lambda p, c, t: decode_step(p, c, t, cfg, rcfg, PCTX))(params,
                                                               caches, toks)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # Each MoE layer routes every token's top-k pairs: held or dropped.
    routed = [B * cfg.moe.top_k if k.endswith("+moe") else 0
              for k in layer_kinds(cfg)]
    np.testing.assert_array_equal(
        np.asarray(counters.held) + np.asarray(counters.drops), routed)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_exact_published_dims(arch):
    """Configs carry the exact published dimensions (spot-check table)."""
    cfg = get_config(arch)
    expect = {
        "mamba2-130m": dict(num_layers=24, d_model=768, vocab_size=50280),
        "qwen2-72b": dict(num_layers=80, d_model=8192, num_heads=64,
                          num_kv_heads=8, d_ff=29568, vocab_size=152064),
        "qwen3-0.6b": dict(num_layers=28, d_model=1024, num_heads=16,
                           num_kv_heads=8, d_ff=3072, vocab_size=151936),
        "mistral-large-123b": dict(num_layers=88, d_model=12288,
                                   num_heads=96, num_kv_heads=8,
                                   d_ff=28672, vocab_size=32768),
        "internlm2-1.8b": dict(num_layers=24, d_model=2048, num_heads=16,
                               num_kv_heads=8, d_ff=8192, vocab_size=92544),
        "jamba-v0.1-52b": dict(num_layers=32, d_model=4096, num_heads=32,
                               num_kv_heads=8, d_ff=14336,
                               vocab_size=65536),
        "hubert-xlarge": dict(num_layers=48, d_model=1280, num_heads=16,
                              num_kv_heads=16, d_ff=5120, vocab_size=504),
        "internvl2-26b": dict(num_layers=48, d_model=6144, num_heads=48,
                              num_kv_heads=8, d_ff=16384, vocab_size=92553),
        "dbrx-132b": dict(num_layers=40, d_model=6144, num_heads=48,
                          num_kv_heads=8, vocab_size=100352),
        "deepseek-v3-671b": dict(num_layers=61, d_model=7168,
                                 num_heads=128, vocab_size=129280),
    }[arch]
    for k, v in expect.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    moe_expect = {
        "jamba-v0.1-52b": (16, 2), "dbrx-132b": (16, 4),
        "deepseek-v3-671b": (256, 8),
    }
    if arch in moe_expect:
        assert (cfg.moe.num_experts, cfg.moe.top_k) == moe_expect[arch]


def test_shape_skips_documented():
    """Skips match the brief: long_500k only for ssm/hybrid; decode only
    for causal archs."""
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        shapes = supported_shapes(cfg)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in shapes, arch
        else:
            assert "long_500k" not in shapes, arch
        if not cfg.has_decode:
            assert "decode_32k" not in shapes, arch
    total = sum(len(supported_shapes(get_config(a))) for a in ASSIGNED_ARCHS)
    assert total == 31  # 40 cells minus documented skips


def test_jamba_interleave_pattern():
    kinds = layer_kinds(get_config("jamba-v0.1-52b"))
    attn_layers = [i for i, k in enumerate(kinds) if k.startswith("attn")]
    assert attn_layers == [4, 12, 20, 28]          # 1:7 interleave
    moe_layers = [i for i, k in enumerate(kinds) if k.endswith("moe")]
    assert moe_layers == list(range(1, 32, 2))     # every other layer


def test_deepseek_pattern():
    kinds = layer_kinds(get_config("deepseek-v3-671b"))
    assert kinds[:3] == ["attn+dense"] * 3
    assert all(k == "attn+moe" for k in kinds[3:])
