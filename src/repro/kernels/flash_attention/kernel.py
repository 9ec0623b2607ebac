"""Flash-attention Pallas kernel (forward), TPU BlockSpec tiling.

Grid (B*H, Sq/bq, Sk/bk) with the KV dimension innermost: each (batch*head,
q-block) owns VMEM scratch for the running max/denominator/accumulator and
streams KV blocks through VMEM.  Causal q-blocks that lie entirely above the
diagonal are skipped via ``pl.when`` (no MXU work issued), giving the ~2x
causal saving the paper-grade kernels get.

Block shapes default to (bq, d) = (256, head_dim) and bk = 512; head_dim is
the lane dimension (128-aligned on the assigned archs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_fwd_pallas"]

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, scale: float, bq: int, bk: int, k_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # Skip fully-masked blocks (strictly above the causal diagonal).
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale           # (bq, d)
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0].astype(jnp.float32)                   # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk"))
def flash_fwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, bq: int = 256,
                     bk: int = 512) -> jax.Array:
    """q/k/v: (BH, S, d) with heads pre-flattened into the batch dim."""
    BH, Sq, d = q.shape
    Sk = k.shape[1]
    bq, bk = min(bq, Sq), min(bk, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"S ({Sq},{Sk}) not divisible by blocks ({bq},{bk})")
    k_steps = Sk // bk
    grid = (BH, Sq // bq, k_steps)
    scale = d ** -0.5
    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=scale, bq=bq, bk=bk,
                          k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )(q, k, v)
