"""Attention: GQA (optional QKV-bias / qk-norm) and DeepSeek MLA.

The core softmax-attention primitive is a *chunked flash reference*
(``flash_ref``): an online-softmax ``lax.scan`` over KV blocks that never
materialises the (S, S) score matrix -- this is what the dry-runs compile
(memory-bounded at 32k/500k context) and what the Pallas flash kernel is
validated against.  It reads K and V where they are stored: each block is a
slice of the stored array, at its KV heads and in its stored dtype (the
bf16 cache), against the queries grouped per KV head, with float32
accumulation and a float32 softmax.  The cache is never widened, repeated
to the query heads or padded to whole blocks.  Decode attends one new query
against a KV cache; under pjit the cache's sequence axis is sharded over the
``model`` mesh axis and GSPMD inserts the cross-shard softmax reductions
(flash-decode).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rotary, rms_norm, rotary_cos_sin

__all__ = [
    "AttnConfig",
    "GQAParams",
    "MLAParams",
    "KVCache",
    "flash_ref",
    "init_gqa",
    "init_mla",
    "gqa_attention",
    "mla_attention",
    "gqa_prefill",
    "mla_prefill",
    "gqa_decode",
    "mla_decode",
]


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    causal: bool = True
    qkv_bias: bool = False           # qwen2
    qk_norm: bool = False            # qwen3
    rope_theta: float = 10000.0
    # MLA (deepseek-v3) dims; attention is MLA iff q_lora_rank > 0.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0


class GQAParams(NamedTuple):
    wq: jax.Array                 # (D, H*hd)
    wk: jax.Array                 # (D, Hkv*hd)
    wv: jax.Array                 # (D, Hkv*hd)
    wo: jax.Array                 # (H*hd, D)
    bq: jax.Array | None = None
    bk: jax.Array | None = None
    bv: jax.Array | None = None
    q_norm: jax.Array | None = None   # (hd,)
    k_norm: jax.Array | None = None


class MLAParams(NamedTuple):
    wq_a: jax.Array               # (D, q_lora)
    q_a_norm: jax.Array           # (q_lora,)
    wq_b: jax.Array               # (q_lora, H*(nope+rope))
    wkv_a: jax.Array              # (D, kv_lora + rope)
    kv_a_norm: jax.Array          # (kv_lora,)
    wkv_b: jax.Array              # (kv_lora, H*(nope+v))
    wo: jax.Array                 # (H*v, D)


class KVCache(NamedTuple):
    """Decode-time cache.  GQA: k/v (B, S, Hkv, hd).  MLA: latent
    (B, S, kv_lora) and rope key (B, S, rope_dim)."""

    k: jax.Array
    v: jax.Array
    length: jax.Array             # () int32 filled positions


def init_gqa(key: jax.Array, cfg: AttnConfig, dtype=jnp.float32) -> GQAParams:
    D, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = D ** -0.5
    return GQAParams(
        wq=jax.random.normal(ks[0], (D, H * hd), dtype) * s,
        wk=jax.random.normal(ks[1], (D, Hkv * hd), dtype) * s,
        wv=jax.random.normal(ks[2], (D, Hkv * hd), dtype) * s,
        wo=jax.random.normal(ks[3], (H * hd, D), dtype) * (H * hd) ** -0.5,
        bq=jnp.zeros((H * hd,), dtype) if cfg.qkv_bias else None,
        bk=jnp.zeros((Hkv * hd,), dtype) if cfg.qkv_bias else None,
        bv=jnp.zeros((Hkv * hd,), dtype) if cfg.qkv_bias else None,
        q_norm=jnp.ones((hd,), dtype) if cfg.qk_norm else None,
        k_norm=jnp.ones((hd,), dtype) if cfg.qk_norm else None,
    )


def init_mla(key: jax.Array, cfg: AttnConfig, dtype=jnp.float32) -> MLAParams:
    D, H = cfg.d_model, cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 5)
    s = D ** -0.5
    return MLAParams(
        wq_a=jax.random.normal(ks[0], (D, cfg.q_lora_rank), dtype) * s,
        q_a_norm=jnp.ones((cfg.q_lora_rank,), dtype),
        wq_b=jax.random.normal(ks[1], (cfg.q_lora_rank, H * qk), dtype)
        * cfg.q_lora_rank ** -0.5,
        wkv_a=jax.random.normal(
            ks[2], (D, cfg.kv_lora_rank + cfg.qk_rope_dim), dtype
        )
        * s,
        kv_a_norm=jnp.ones((cfg.kv_lora_rank,), dtype),
        wkv_b=jax.random.normal(
            ks[3], (cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim)),
            dtype,
        )
        * cfg.kv_lora_rank ** -0.5,
        wo=jax.random.normal(ks[4], (H * cfg.v_head_dim, D), dtype)
        * (H * cfg.v_head_dim) ** -0.5,
    )


def flash_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    block_kv: int = 512,
    q_offset: jax.Array | int = 0,
    kv_valid_len: jax.Array | None = None,
    scale: float | None = None,
    unroll: bool = False,
) -> jax.Array:
    """Online-softmax attention over KV blocks (pure-jnp flash).

    K and V are read in place: each step slices one block of the stored
    arrays, at their ``Hkv`` heads and in their stored dtype, and contracts
    it against the queries grouped per KV head (q viewed as (B, Sq, Hkv,
    rep, hd)) with float32 accumulation.  q is scaled in float32 and then
    cast to k's dtype, so a bf16 cache meets bf16 queries on the MXU; the
    softmax state (m, l, acc) stays float32.  When ``block_kv`` does not
    divide Sk, the last block is read from the clamped start Sk - block_kv
    and its positions that the previous block already covered are masked,
    so no padded copy is made.

    Args:
      q: (B, Sq, H, hd); k/v: (B, Sk, Hkv, hd_k/hd_v) with H % Hkv == 0.
      causal: causal masking with absolute positions (q position i attends
        kv position j iff j <= i + q_offset).
      q_offset: absolute position of q[0] (decode: cache length); () or (B,).
      kv_valid_len: optional bound on valid kv positions (decode cache);
        () or (B,).
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    hv = v.shape[-1]
    blk = min(block_kv, Sk)
    nblk = -(-Sk // blk)
    scale = scale if scale is not None else hd ** -0.5
    qg = (jnp.asarray(q, jnp.float32) * scale).astype(k.dtype)
    qg = qg.reshape(B, Sq, Hkv, rep, hd)

    q_offset = jnp.asarray(q_offset)
    if q_offset.ndim == 0:
        q_offset = q_offset[None]                            # (1,) or (B,)
    q_pos = jnp.arange(Sq)[None, :] + q_offset[:, None]      # (B?, Sq)
    if kv_valid_len is None:
        limit = jnp.full((1,), Sk)
    else:
        limit = jnp.asarray(kv_valid_len)
        if limit.ndim == 0:
            limit = limit[None]                              # (1,) or (B,)

    def body(carry, start):
        m, l, acc = carry
        # The last block of a ragged Sk starts early; positions before
        # ``start`` were the previous block's and are masked here.
        first = jnp.minimum(start, Sk - blk)
        kb = jax.lax.dynamic_slice_in_dim(k, first, blk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, blk, axis=1)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kb,
                       preferred_element_type=jnp.float32)
        kv_pos = first + jnp.arange(blk)
        mask = ((kv_pos >= start)[None, None, :]
                & (kv_pos[None, None, :] < limit[:, None, None]))
        if causal:
            mask = mask & (kv_pos[None, None, :] <= q_pos[:, :, None])
        s = jnp.where(mask[:, None, None], s, -jnp.inf)     # (B, g, r, Sq, blk)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bgrqk,bkgv->bgrqv", p.astype(v.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, rep, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, rep, Sq, hv), jnp.float32)
    starts = jnp.arange(nblk) * blk
    if unroll:
        # Analysis mode: python loop so cost_analysis sees every block
        # (XLA counts while bodies once -- see roofline/analysis.py).
        carry = (m0, l0, a0)
        for i in range(nblk):
            carry, _ = body(carry, starts[i])
        m, l, acc = carry
    else:
        # Checkpointed body: the backward pass recomputes each block's
        # (B, H, Sq, blk) scores instead of keeping all of them.  Training
        # one qwen3-235b-a22b layer on 4096-token sequences over 4 v5e
        # chips, that is 15.0 GB of temp per chip instead of 20.4 GB.
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(body, prevent_cse=False), (m0, l0, a0), starts)
    out = acc / jnp.maximum(l[..., None], 1e-20)            # (B, g, r, Sq, hv)
    out = out.reshape(B, H, Sq, hv)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)          # (B, Sq, H, hv)


def _update_at(cache_arr: jax.Array, new: jax.Array,
               lengths: jax.Array) -> jax.Array:
    """Batched dynamic_update_slice along axis 1 at per-row offsets.

    cache_arr: (B, S, ...); new: (B, C, ...); lengths: (B,) write offsets.
    """
    def one(c, n, off):
        return jax.lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype),
                                                   off, axis=0)

    return jax.vmap(one)(cache_arr, new, lengths)


def _project_gqa(x, params: GQAParams, cfg: AttnConfig):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ params.wq
    k = x @ params.wk
    v = x @ params.wv
    if cfg.qkv_bias:
        q, k, v = q + params.bq, k + params.bk, v + params.bv
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params.q_norm)
        k = rms_norm(k, params.k_norm)
    return q, k, v


def gqa_attention(
    x: jax.Array,
    params: GQAParams,
    cfg: AttnConfig,
    *,
    positions: jax.Array | None = None,
    block_kv: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Full-sequence GQA (training / prefill).  x: (B, S, D)."""
    B, S, _ = x.shape
    q, k, v = _project_gqa(x, params, cfg)
    pos = jnp.arange(S) if positions is None else positions
    cos, sin = rotary_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    out = flash_ref(q, k, v, causal=cfg.causal, block_kv=block_kv,
                    unroll=unroll)
    return out.reshape(B, S, -1) @ params.wo


def gqa_prefill(
    x: jax.Array,
    cache: KVCache,
    params: GQAParams,
    cfg: AttnConfig,
    *,
    valid_len: jax.Array | int | None = None,
    block_kv: int = 1024,
    unroll: bool = False,
) -> tuple[jax.Array, KVCache]:
    """Chunked prefill: attend a chunk against cache + itself, write cache.

    x: (B, C, D) chunk starting at absolute position cache.length.
    valid_len: tokens of the chunk that are real (rest are right-padding).
    """
    B, C, _ = x.shape
    q, k, v = _project_gqa(x, params, cfg)
    pos = cache.length[:, None] + jnp.arange(C)[None, :]     # (B, C)
    cos, sin = rotary_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    k_all = _update_at(cache.k, k, cache.length)
    v_all = _update_at(cache.v, v, cache.length)
    vl = C if valid_len is None else valid_len
    out = flash_ref(q, k_all, v_all, causal=True, block_kv=block_kv,
                    q_offset=cache.length, kv_valid_len=cache.length + vl,
                    unroll=unroll)
    y = out.reshape(B, C, -1) @ params.wo
    return y, KVCache(k_all, v_all, cache.length + vl)


def gqa_decode(
    x: jax.Array,
    cache: KVCache,
    params: GQAParams,
    cfg: AttnConfig,
    *,
    block_kv: int = 1024,
    unroll: bool = False,
) -> tuple[jax.Array, KVCache]:
    """One-token decode with a static-shape KV cache.  x: (B, 1, D)."""
    B = x.shape[0]
    q, k, v = _project_gqa(x, params, cfg)
    pos = cache.length[:, None]                              # (B, 1)
    cos, sin = rotary_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    k_all = _update_at(cache.k, k, cache.length)
    v_all = _update_at(cache.v, v, cache.length)
    out = flash_ref(
        q, k_all, v_all, causal=False, block_kv=block_kv,
        kv_valid_len=cache.length + 1, unroll=unroll,
    )
    y = out.reshape(B, 1, -1) @ params.wo
    return y, KVCache(k_all, v_all, cache.length + 1)


def _project_mla(x, params: MLAParams, cfg: AttnConfig, pos: jax.Array):
    """Returns per-head q (nope+rope), latent c_kv, rope key k_r."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = rms_norm(x @ params.wq_a, params.q_a_norm) @ params.wq_b
    q = q.reshape(B, S, H, nope + rope)
    kv = x @ params.wkv_a                                   # (B,S,lora+rope)
    c_kv = rms_norm(kv[..., : cfg.kv_lora_rank], params.kv_a_norm)
    k_r = kv[..., cfg.kv_lora_rank :].reshape(B, S, 1, rope)
    cos, sin = rotary_cos_sin(pos, rope, cfg.rope_theta)
    q_r = apply_rotary(q[..., nope:], cos, sin)
    k_r = apply_rotary(k_r, cos, sin)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    return q, c_kv, k_r[:, :, 0, :]


def mla_attention(
    x: jax.Array,
    params: MLAParams,
    cfg: AttnConfig,
    *,
    block_kv: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """MLA prefill/training: expand latent to per-head K/V (chunk-bounded
    via flash blocks).  x: (B, S, D)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope, hv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pos = jnp.arange(S)
    q, c_kv, k_r = _project_mla(x, params, cfg, pos)
    kv = (c_kv @ params.wkv_b).reshape(B, S, H, nope + hv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (B, S, H, rope))],
        axis=-1,
    )
    v = kv[..., nope:]
    out = flash_ref(q, k, v, causal=cfg.causal, block_kv=block_kv,
                    scale=(nope + rope) ** -0.5, unroll=unroll)
    return out.reshape(B, S, -1) @ params.wo


def mla_prefill(
    x: jax.Array,
    cache: KVCache,
    params: MLAParams,
    cfg: AttnConfig,
    *,
    valid_len: jax.Array | int | None = None,
    block_kv: int = 1024,
    unroll: bool = False,
) -> tuple[jax.Array, KVCache]:
    """Chunked MLA prefill on the latent cache.  x: (B, C, D)."""
    B, C, _ = x.shape
    H = cfg.num_heads
    nope, rope, hv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pos = cache.length[:, None] + jnp.arange(C)[None, :]
    q, c_new, kr_new = _project_mla(x, params, cfg, pos)
    c_all = _update_at(cache.k, c_new, cache.length)
    kr_all = _update_at(cache.v, kr_new, cache.length)
    S = c_all.shape[1]
    kv = (c_all @ params.wkv_b).reshape(B, S, H, nope + hv)
    k_full = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr_all[:, :, None, :], (B, S, H, rope))], axis=-1)
    v_full = kv[..., nope:]
    vl = C if valid_len is None else valid_len
    out = flash_ref(q, k_full, v_full, causal=True, block_kv=block_kv,
                    q_offset=cache.length, kv_valid_len=cache.length + vl,
                    scale=(nope + rope) ** -0.5, unroll=unroll)
    y = out.reshape(B, C, -1) @ params.wo
    return y, KVCache(c_all, kr_all, cache.length + vl)


def mla_decode(
    x: jax.Array,
    cache: KVCache,
    params: MLAParams,
    cfg: AttnConfig,
) -> tuple[jax.Array, KVCache]:
    """Absorbed-weight MLA decode on the latent cache (cache-efficient form).

    cache.k: (B, S, kv_lora) latent; cache.v: (B, S, rope) rope keys.
    Scores: s_t = q_nope^T W_UK c_t + q_rope^T k_rope_t, computed without
    expanding per-head K/V.
    """
    B = x.shape[0]
    H = cfg.num_heads
    nope, rope, hv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    pos = cache.length[:, None]                              # (B, 1)
    q, c_new, kr_new = _project_mla(x, params, cfg, pos)
    c_all = _update_at(cache.k, c_new, cache.length)
    kr_all = _update_at(cache.v, kr_new, cache.length)
    w_full = params.wkv_b.reshape(lora, H, nope + hv)
    w_uk = w_full[..., :nope]
    w_uv = w_full[..., nope:]
    # Absorb W_UK into q: (B, 1, H, nope) x (lora, H, nope) -> (B, H, lora)
    q_abs = jnp.einsum("bqhn,lhn->bhl", q[..., :nope].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scores = jnp.einsum("bhl,bsl->bhs", q_abs, c_all.astype(jnp.float32))
    scores += jnp.einsum("bqhr,bsr->bhs", q[..., nope:].astype(jnp.float32),
                         kr_all.astype(jnp.float32))
    scores *= (nope + rope) ** -0.5
    S = c_all.shape[1]
    mask = jnp.arange(S)[None, None, :] <= cache.length[:, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsl->bhl", p, c_all.astype(jnp.float32))  # latent ctx
    out = jnp.einsum("bhl,lhv->bhv", ctx, w_uv.astype(jnp.float32))
    y = out.reshape(B, 1, H * hv).astype(x.dtype) @ params.wo
    return y, KVCache(c_all, kr_all, cache.length + 1)
