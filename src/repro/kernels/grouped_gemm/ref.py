"""Oracles for the grouped GEMM kernels: dense per-group products."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grouped_ffn_ref", "stack_layer", "grouped_matmul_q8_ref",
           "grouped_swiglu_q8_ref"]


def stack_layer(ws, layer: jax.Array | None) -> tuple[jax.Array, ...]:
    """Layer ``layer`` of each (L, G, ...) weight stack in ``ws``, or the
    weights as they are when ``layer`` is None."""
    if layer is None:
        return tuple(ws)
    return tuple(jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                 for w in ws)


def grouped_ffn_ref(xs: jax.Array, w1: jax.Array, w3: jax.Array,
                    w2: jax.Array) -> jax.Array:
    """Dense per-slot SwiGLU over every row: xs (G, C, D), w1/w3 (G, D, F),
    w2 (G, F, D) -> (G, C, D), each product in the operands' dtype."""
    h = jnp.einsum("gcd,gdf->gcf", xs, w1)
    g = jnp.einsum("gcd,gdf->gcf", xs, w3)
    return jnp.einsum("gcf,gfd->gcd", jax.nn.silu(h) * g, w2)


def grouped_matmul_q8_ref(q: jax.Array, row_scale: jax.Array, wq: jax.Array,
                          col_scale: jax.Array) -> jax.Array:
    """w8a8 grouped matmul oracle: int32 accumulate, dequant at the end.

    q: (G, M, K) int8 codes with per-row fp32 scales row_scale (G, M);
    wq: (G, K, N) int8 codes with per-column scales col_scale (G, N).
    Returns (G, M, N) fp32 = acc * row_scale ⊗ col_scale -- the rank-1
    dequant the Pallas kernel applies on its final K step.
    """
    acc = jnp.einsum("gmk,gkn->gmn", q, wq,
                     preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32)
            * row_scale[:, :, None] * col_scale[:, None, :])


def grouped_swiglu_q8_ref(q: jax.Array, row_scale: jax.Array,
                          w1q: jax.Array, w1s: jax.Array,
                          w3q: jax.Array, w3s: jax.Array) -> jax.Array:
    """w8a8 grouped SwiGLU oracle: both contractions int8, gate in fp32."""
    h = grouped_matmul_q8_ref(q, row_scale, w1q, w1s)
    g = grouped_matmul_q8_ref(q, row_scale, w3q, w3s)
    return jax.nn.silu(h) * g
