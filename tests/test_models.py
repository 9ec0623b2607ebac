"""Model primitives: flash oracle, decode/prefill consistency, SSD math."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    AttnConfig,
    KVCache,
    flash_ref,
    gqa_attention,
    gqa_decode,
    gqa_prefill,
    init_gqa,
    init_mla,
    mla_attention,
    mla_decode,
    mla_prefill,
)
from repro.models.ssm import (
    SSMConfig,
    SSMState,
    _conv_channels,
    init_ssm,
    ssd_decode,
    ssd_forward,
    ssd_prefill,
)

B, S = 2, 36


def _naive_attn(q, k, v, causal, rep, q_offset=0, kv_valid_len=None):
    """Dense float32 attention over repeated KV heads.  q_offset and
    kv_valid_len are () or (B,), as flash_ref takes them."""
    q, k, v = (jnp.asarray(a, jnp.float32) for a in (q, k, v))
    kf = jnp.repeat(k, rep, 2)
    vf = jnp.repeat(v, rep, 2)
    Sq, Sk, d = q.shape[1], k.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf,
                   precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    off = jnp.broadcast_to(jnp.asarray(q_offset), (q.shape[0],))
    lim = jnp.broadcast_to(
        jnp.asarray(Sk if kv_valid_len is None else kv_valid_len),
        (q.shape[0],))
    j = jnp.arange(Sk)[None, None, :]
    m = j < lim[:, None, None]                               # (B, Sq, Sk)
    if causal:
        m = m & (j <= jnp.arange(Sq)[None, :, None] + off[:, None, None])
    s = jnp.where(m[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf,
                      precision=jax.lax.Precision.HIGHEST)


# Each case: heads (H, Hkv), query and KV lengths, block, mask, dtype.
# The first four keep the original causal x unroll grid (ids unroll-causal)
# on a KV length of 36 over blocks of 16; the rest cover GQA groups of 1,
# 12 and 16, decode (Sq 1) and chunked prefill at per-row offsets, per-row
# valid lengths, a KV length shorter than one block, and a bf16 cache.
_FLASH_CASES = {
    "True-True": dict(unroll=True, causal=True),
    "True-False": dict(unroll=True, causal=False),
    "False-True": dict(unroll=False, causal=True),
    "False-False": dict(unroll=False, causal=False),
    "rep1-decode-rows": dict(H=4, Hkv=4, Sq=1, Sk=50, causal=False,
                             kv_valid_len=(50, 7)),
    "rep1-decode-scalar-len": dict(H=4, Hkv=4, Sq=1, Sk=50, causal=False,
                                   kv_valid_len=23),
    "rep12-decode-rows": dict(H=24, Hkv=2, Sq=1, Sk=40, causal=False,
                              kv_valid_len=(40, 17)),
    "rep16-decode-rows-unroll": dict(H=32, Hkv=2, Sq=1, Sk=45, causal=False,
                                     kv_valid_len=(3, 45), unroll=True),
    "rep12-prefill-offset": dict(H=24, Hkv=2, Sq=12, Sk=45, causal=True,
                                 q_offset=(20, 5), kv_valid_len=(32, 14)),
    "rep16-prefill-offset-unroll": dict(H=32, Hkv=2, Sq=12, Sk=45,
                                        causal=True, q_offset=(20, 5),
                                        kv_valid_len=(32, 17), unroll=True),
    "rep12-short-kv": dict(H=24, Hkv=2, Sq=6, Sk=10, causal=True,
                           q_offset=(4, 0), kv_valid_len=(10, 6)),
    "rep12-decode-bf16": dict(H=24, Hkv=2, Sq=1, Sk=40, causal=False,
                              kv_valid_len=(40, 17), dtype=jnp.bfloat16),
    "rep16-prefill-bf16": dict(H=32, Hkv=2, Sq=12, Sk=45, causal=True,
                               q_offset=(20, 5), kv_valid_len=(32, 17),
                               dtype=jnp.bfloat16),
    "rep1-prefill-bf16": dict(H=4, Hkv=4, Sq=12, Sk=45, causal=True,
                              q_offset=(33, 0), kv_valid_len=(45, 12),
                              dtype=jnp.bfloat16),
}


def _flash_inputs(H, Hkv, Sq, Sk, hd, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, Sq, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Sk, Hkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Sk, Hkv, hd))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("case", list(_FLASH_CASES), ids=list(_FLASH_CASES))
def test_flash_ref_matches_naive(case):
    c = dict(H=4, Hkv=2, hd=8, Sq=S, Sk=S, block_kv=16, unroll=False,
             q_offset=0, kv_valid_len=None, dtype=jnp.float32)
    c.update(_FLASH_CASES[case])
    q, k, v = _flash_inputs(c["H"], c["Hkv"], c["Sq"], c["Sk"], c["hd"],
                            c["dtype"])
    out = flash_ref(q, k, v, causal=c["causal"], block_kv=c["block_kv"],
                    q_offset=c["q_offset"], kv_valid_len=c["kv_valid_len"],
                    unroll=c["unroll"])
    assert out.shape == q.shape and out.dtype == q.dtype
    # The oracle sees the same (bf16-valued) inputs in float32: only the
    # kernel's own rounding differs.
    ref = _naive_attn(q, k, v, c["causal"], c["H"] // c["Hkv"],
                      q_offset=c["q_offset"], kv_valid_len=c["kv_valid_len"])
    tol = 1e-5 if c["dtype"] == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.array(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("unroll", [False, True])
def test_flash_ref_grad_matches_naive(unroll):
    """Gradients through the checkpointed scan (and the unrolled loop) of
    a grouped (rep 12), ragged (45 = 2 x 16 + 13), offset causal chunk."""
    H, Hkv, Sq, Sk, hd = 24, 2, 12, 45, 8
    q, k, v = _flash_inputs(H, Hkv, Sq, Sk, hd, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (B, Sq, H, hd))
    off, lim = jnp.array([20, 5]), jnp.array([32, 17])

    def loss_flash(q, k, v):
        out = flash_ref(q, k, v, causal=True, block_kv=16, q_offset=off,
                        kv_valid_len=lim, unroll=unroll)
        return (out * w).sum()

    def loss_naive(q, k, v):
        return (_naive_attn(q, k, v, True, H // Hkv, q_offset=off,
                            kv_valid_len=lim) * w).sum()

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.array(g), np.array(r), rtol=1e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_gqa_cache_read_in_place(step):
    """The lowered decode and prefill programs hold no array over the KV
    length (or that length padded to whole blocks) in float32 or with all
    query heads: the bf16 cache is read at its KV heads, not widened or
    repeated."""
    H, Hkv, hd, Sk, blk, Bc = 12, 2, 8, 45, 16, 2          # 45 = 2 x 16 + 13
    cfg = AttnConfig(d_model=16, num_heads=H, num_kv_heads=Hkv, head_dim=hd)
    params = init_gqa(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    cache = KVCache(jnp.zeros((Bc, Sk, Hkv, hd), jnp.bfloat16),
                    jnp.zeros((Bc, Sk, Hkv, hd), jnp.bfloat16),
                    jnp.array([9, 30], jnp.int32))
    if step == "decode":
        x = jnp.zeros((Bc, 1, 16), jnp.bfloat16)
        fn = lambda x, c: gqa_decode(x, c, params, cfg, block_kv=blk)  # noqa: E731
    else:
        x = jnp.zeros((Bc, 5, 16), jnp.bfloat16)
        fn = lambda x, c: gqa_prefill(x, c, params, cfg, block_kv=blk)  # noqa: E731
    text = jax.jit(fn).lower(x, cache).as_text()
    lengths = {Sk, -(-Sk // blk) * blk}
    types = re.findall(r"tensor<((?:\d+x)+)(\w+)>", text)
    assert any(Sk in map(int, d.split("x")[:-1]) for d, _ in types)
    bad = sorted({f"{d}{t}" for d, t in types
                  if lengths & set(map(int, d.split("x")[:-1]))
                  and (t == "f32" or H in map(int, d.split("x")[:-1]))})
    assert not bad, bad


def test_gqa_prefill_decode_consistency():
    cfg = AttnConfig(d_model=16, num_heads=4, num_kv_heads=2, head_dim=8,
                     qkv_bias=True, qk_norm=True)
    params = init_gqa(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, 16))
    x2 = jax.random.normal(jax.random.PRNGKey(5), (B, 1, 16))
    y_full = gqa_attention(x, params, cfg, block_kv=16)
    cache = KVCache(jnp.zeros((B, S + 4, 2, 8)), jnp.zeros((B, S + 4, 2, 8)),
                    jnp.zeros((B,), jnp.int32))
    ys = []
    for c in range(3):
        y_c, cache = gqa_prefill(x[:, c * 12:(c + 1) * 12], cache, params,
                                 cfg, block_kv=16)
        ys.append(y_c)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, 1)),
                               np.array(y_full), rtol=1e-4, atol=1e-4)
    y_d, cache = gqa_decode(x2, cache, params, cfg)
    y_ref = gqa_attention(jnp.concatenate([x, x2], 1), params, cfg,
                          block_kv=16)[:, -1:]
    np.testing.assert_allclose(np.array(y_d), np.array(y_ref), rtol=1e-4,
                               atol=1e-4)


def test_mla_prefill_decode_consistency():
    cfg = AttnConfig(d_model=32, num_heads=4, num_kv_heads=4, head_dim=0,
                     q_lora_rank=16, kv_lora_rank=24, qk_nope_dim=8,
                     qk_rope_dim=4, v_head_dim=8)
    params = init_mla(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, 32))
    x2 = jax.random.normal(jax.random.PRNGKey(7), (B, 1, 32))
    y_full = mla_attention(x, params, cfg, block_kv=16)
    cache = KVCache(jnp.zeros((B, S + 4, 24)), jnp.zeros((B, S + 4, 4)),
                    jnp.zeros((B,), jnp.int32))
    ys = []
    for c in range(3):
        y_c, cache = mla_prefill(x[:, c * 12:(c + 1) * 12], cache, params,
                                 cfg, block_kv=16)
        ys.append(y_c)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, 1)),
                               np.array(y_full), rtol=1e-4, atol=1e-4)
    y_d, _ = mla_decode(x2, cache, params, cfg)
    y_ref = mla_attention(jnp.concatenate([x, x2], 1), params, cfg,
                          block_kv=16)[:, -1:]
    np.testing.assert_allclose(np.array(y_d), np.array(y_ref), rtol=1e-4,
                               atol=1e-4)


def test_ragged_decode_batch():
    """Per-sequence cache lengths: two sequences at different positions
    decode correctly in one batch."""
    cfg = AttnConfig(d_model=16, num_heads=2, num_kv_heads=2, head_dim=8)
    params = init_gqa(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    # seq 0 has 12 tokens of context, seq 1 only 4.
    c0 = KVCache(jnp.zeros((1, 16, 2, 8)), jnp.zeros((1, 16, 2, 8)),
                 jnp.zeros((1,), jnp.int32))
    _, c0 = gqa_prefill(x[:1], c0, params, cfg, block_kv=16)
    c1 = KVCache(jnp.zeros((1, 16, 2, 8)), jnp.zeros((1, 16, 2, 8)),
                 jnp.zeros((1,), jnp.int32))
    _, c1 = gqa_prefill(x[1:, :4], c1, params, cfg, block_kv=16)
    cache = KVCache(jnp.concatenate([c0.k, c1.k]),
                    jnp.concatenate([c0.v, c1.v]),
                    jnp.concatenate([c0.length, c1.length]))
    x2 = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 16))
    y, _ = gqa_decode(x2, cache, params, cfg)
    # references with per-sequence contexts
    y0 = gqa_attention(jnp.concatenate([x[:1], x2[:1]], 1), params,
                       cfg)[:, -1:]
    y1 = gqa_attention(jnp.concatenate([x[1:, :4], x2[1:]], 1), params,
                       cfg)[:, -1:]
    np.testing.assert_allclose(np.array(y[0]), np.array(y0[0]), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.array(y[1]), np.array(y1[0]), rtol=1e-4,
                               atol=1e-4)


def test_ssd_chunked_equals_sequential():
    cfg = SSMConfig(d_model=24, d_inner=32, headdim=8, d_state=16,
                    n_groups=2, chunk=8)
    params = init_ssm(jax.random.PRNGKey(7), cfg)
    L = 32
    x = jax.random.normal(jax.random.PRNGKey(8), (B, L, 24)) * 0.5
    y_chunk, final = ssd_forward(x, params, cfg)
    st = SSMState(jnp.zeros((B, cfg.n_heads, cfg.d_state, cfg.headdim)),
                  jnp.zeros((B, cfg.d_conv - 1, _conv_channels(cfg))),
                  jnp.zeros((B,), jnp.int32))
    ys = []
    for t in range(L):
        y_t, st = ssd_decode(x[:, t:t + 1], st, params, cfg)
        ys.append(y_t)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, 1)),
                               np.array(y_chunk), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.array(final), np.array(st.s), rtol=2e-4,
                               atol=2e-4)


def test_ssd_prefill_continues_state():
    cfg = SSMConfig(d_model=24, d_inner=32, headdim=8, d_state=16,
                    n_groups=2, chunk=8)
    params = init_ssm(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (B, 32, 24)) * 0.5
    y_full, final = ssd_forward(x, params, cfg)
    st = SSMState(jnp.zeros((B, cfg.n_heads, cfg.d_state, cfg.headdim)),
                  jnp.zeros((B, cfg.d_conv - 1, _conv_channels(cfg))),
                  jnp.zeros((B,), jnp.int32))
    ys = []
    for c in range(2):
        y_c, st = ssd_prefill(x[:, c * 16:(c + 1) * 16], st, params, cfg)
        ys.append(y_c)
    np.testing.assert_allclose(np.array(jnp.concatenate(ys, 1)),
                               np.array(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.array(st.s), np.array(final), rtol=2e-4,
                               atol=2e-4)
