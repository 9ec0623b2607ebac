"""Public flash-attention op with GQA head expansion + layout handling."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_fwd_pallas

__all__ = ["flash_attention"]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 256,
                    bk: int = 512) -> jax.Array:
    """q: (B, Sq, H, d); k/v: (B, Sk, Hkv, d).  Returns (B, Sq, H, d)."""
    B, Sq, H, d = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, d)
    out = flash_fwd_pallas(qf, kf, vf, causal=causal, bq=min(bq, Sq),
                           bk=min(bk, Sk))
    return out.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)
