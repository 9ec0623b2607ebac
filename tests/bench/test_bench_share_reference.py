"""A four-layer qwen3-shaped model holding one block of its router's
experts, served as the program serves it (chunked prefill, then decode
through the cache), against the held-block reference's full forward pass.

The logits of every prompt position and of every decoded token are
compared.  The program computes in float32 here, as the reference does;
the same comparison of the program in bfloat16 must fail.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, serve_share
from bench.reference_share import ShareReference

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "tests/bench/data/tiny-share.json").read_text())
SEED = 2**31 + 77
PROMPT, CHUNK, DECODE = 40, 32, 6
# Float32 on both sides, each summing in its own order (the flash scan in
# blocks, the experts' contributions, the reference's blocks of experts):
# 2.4e-6 at logits of scale 4 on this CPU.  The bound leaves forty times
# that; bfloat16 activations put the program 8e-2 away.
ATOL = 1e-4


def _served(config):
    """(logits of every position, the sequence) as the program serves
    it: prompt chunks of CHUNK tokens, then DECODE greedy decode steps."""
    from repro.models.model import decode_step, init_caches, prefill_step
    from repro.models.transformer import ParallelCtx

    cfg = serve_share.model_config(config)
    rcfg = model.runtime_config(
        config, balancer="ultraep", cf_pair=1.0,
        cf_slot=(cfg.moe.held + cfg.moe.n_slot) / cfg.moe.top_k)
    pctx = ParallelCtx(mesh=None)
    params = model.make_params(SEED, cfg, rcfg, pctx)
    prefill = jax.jit(lambda p, c, t, v: prefill_step(
        p, c, t, cfg, rcfg, pctx, valid_len=v))
    decode = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, rcfg, pctx))
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, PROMPT).astype(np.int32)
    caches = init_caches(cfg, 1, 2 * CHUNK + DECODE, rcfg)
    rows = []
    for s in range(0, PROMPT, CHUNK):
        n = min(CHUNK, PROMPT - s)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :n] = prompt[s:s + n]
        logits, caches, _ = prefill(params, caches, jnp.asarray(toks),
                                    jnp.asarray(n, jnp.int32))
        rows.append(np.asarray(logits[0, :n], np.float32))
    out = []
    for _ in range(DECODE):
        out.append(int(rows[-1][-1].argmax()))
        logits, caches, _ = decode(params, caches,
                                   jnp.asarray([[out[-1]]], jnp.int32))
        rows.append(np.asarray(logits[0], np.float32))
    return np.concatenate(rows), np.concatenate([prompt, out])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_logits_agree_with_the_share_reference(dtype):
    config = dict(TINY, program=dict(TINY["program"], dtype=dtype))
    got, seq = _served(config)
    want = ShareReference(config, SEED).logits(seq, np.arange(len(seq)))
    assert got.shape == want.shape == (PROMPT + DECODE, TINY["vocab_size"])
    err = float(np.abs(got - want).max())
    if dtype == "float32":
        assert err <= ATOL
    else:
        assert err > 10 * ATOL


def test_reference_routes_over_the_router_and_keeps_the_block():
    ref = ShareReference(TINY, SEED)
    w = ref.layer_weights(0)
    assert w["router"].shape == (128, 32)
    assert w["w1"].shape == (8, 128, 64)
    # The router is the program's leaf at the router's width.
    want = model.weight(SEED, "layer0.moe.router", (128, 32), jnp.float32)
    np.testing.assert_array_equal(np.asarray(w["router"]), np.asarray(want))
