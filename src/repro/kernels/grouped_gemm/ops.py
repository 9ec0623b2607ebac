"""Public grouped-matmul ops: pad to block multiples, call the Pallas kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.grouped_gemm.kernel import (
    grouped_matmul_pallas,
    grouped_matmul_q8_pallas,
    grouped_swiglu_pallas,
    grouped_swiglu_q8_pallas,
)
from repro.kernels.grouped_gemm.ref import (
    grouped_matmul_q8_ref,
    grouped_matmul_ref,
    grouped_swiglu_q8_ref,
    grouped_swiglu_ref,
)

__all__ = ["grouped_matmul", "grouped_swiglu", "grouped_matmul_q8",
           "grouped_swiglu_q8"]


def _pad_to(v: int, m: int) -> int:
    return -(-v // m) * m


def grouped_matmul(x: jax.Array, w: jax.Array, *, bm: int = 128,
                   bn: int = 128, bk: int = 128) -> jax.Array:
    """Grouped matmul with automatic padding to block multiples.

    Falls back to the jnp oracle for shapes too small to tile profitably.
    """
    G, M, K = x.shape
    _, _, N = w.shape
    if M * N * K < 128 ** 3:  # tiny: tiling overhead dominates
        return grouped_matmul_ref(x, w)
    bm2, bn2, bk2 = min(bm, _pad_to(M, 8)), min(bn, _pad_to(N, 128)), \
        min(bk, _pad_to(K, 128))
    Mp, Np, Kp = _pad_to(M, bm2), _pad_to(N, bn2), _pad_to(K, bk2)
    xp = jnp.pad(x, ((0, 0), (0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N)))
    out = grouped_matmul_pallas(xp, wp, bm=bm2, bn=bn2, bk=bk2)
    return out[:, :M, :N]


def grouped_swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, *,
                   bm: int = 128, bn: int = 128, bk: int = 128) -> jax.Array:
    """Fused ``silu(x@w1) * (x@w3)`` with automatic padding to block multiples.

    One kernel invocation reads each x block once for both contractions and
    keeps the h/g intermediates in VMEM (vs two grouped GEMMs + an
    elementwise pass that round-trips them through HBM).  jnp oracle for
    sub-tile shapes.
    Zero-padding is safe: silu(0) * 0 == 0 on the padded rows/cols.
    """
    G, M, K = x.shape
    _, _, N = w1.shape
    if M * N * K < 128 ** 3:  # tiny: tiling overhead dominates
        return grouped_swiglu_ref(x, w1, w3)
    bm2, bn2, bk2 = min(bm, _pad_to(M, 8)), min(bn, _pad_to(N, 128)), \
        min(bk, _pad_to(K, 128))
    Mp, Np, Kp = _pad_to(M, bm2), _pad_to(N, bn2), _pad_to(K, bk2)
    xp = jnp.pad(x, ((0, 0), (0, Mp - M), (0, Kp - K)))
    w1p = jnp.pad(w1, ((0, 0), (0, Kp - K), (0, Np - N)))
    w3p = jnp.pad(w3, ((0, 0), (0, Kp - K), (0, Np - N)))
    out = grouped_swiglu_pallas(xp, w1p, w3p, bm=bm2, bn=bn2, bk=bk2)
    return out[:, :M, :N]


def grouped_matmul_q8(q: jax.Array, row_scale: jax.Array, wq: jax.Array,
                      col_scale: jax.Array, *, bm: int = 128, bn: int = 128,
                      bk: int = 128) -> jax.Array:
    """w8a8 grouped matmul with automatic padding to block multiples.

    Zero-padding is exact: padded int8 rows/columns are zero codes, so the
    int32 accumulator is zero there and any padded scale dequantizes to 0.
    The M tile floor is 32 (int8 min sublane tile on TPU, vs 8 for fp32).
    """
    G, M, K = q.shape
    _, _, N = wq.shape
    if M * N * K < 128 ** 3:  # tiny: tiling overhead dominates
        return grouped_matmul_q8_ref(q, row_scale, wq, col_scale)
    bm2, bn2, bk2 = min(bm, _pad_to(M, 32)), min(bn, _pad_to(N, 128)), \
        min(bk, _pad_to(K, 128))
    Mp, Np, Kp = _pad_to(M, bm2), _pad_to(N, bn2), _pad_to(K, bk2)
    qp = jnp.pad(q, ((0, 0), (0, Mp - M), (0, Kp - K)))
    wp = jnp.pad(wq, ((0, 0), (0, Kp - K), (0, Np - N)))
    rs = jnp.pad(row_scale, ((0, 0), (0, Mp - M)))
    cs = jnp.pad(col_scale, ((0, 0), (0, Np - N)))
    out = grouped_matmul_q8_pallas(qp, rs, wp, cs, bm=bm2, bn=bn2, bk=bk2)
    return out[:, :M, :N]


def grouped_swiglu_q8(q: jax.Array, row_scale: jax.Array,
                      w1q: jax.Array, w1s: jax.Array,
                      w3q: jax.Array, w3s: jax.Array, *, bm: int = 128,
                      bn: int = 128, bk: int = 128) -> jax.Array:
    """w8a8 fused SwiGLU with automatic padding to block multiples.

    Padding is safe for the gate too: h == g == 0 on padded rows/cols and
    ``0 * logistic(0) * 0 == 0``.
    """
    G, M, K = q.shape
    _, _, N = w1q.shape
    if M * N * K < 128 ** 3:  # tiny: tiling overhead dominates
        return grouped_swiglu_q8_ref(q, row_scale, w1q, w1s, w3q, w3s)
    bm2, bn2, bk2 = min(bm, _pad_to(M, 32)), min(bn, _pad_to(N, 128)), \
        min(bk, _pad_to(K, 128))
    Mp, Np, Kp = _pad_to(M, bm2), _pad_to(N, bn2), _pad_to(K, bk2)
    qp = jnp.pad(q, ((0, 0), (0, Mp - M), (0, Kp - K)))
    w1p = jnp.pad(w1q, ((0, 0), (0, Kp - K), (0, Np - N)))
    w3p = jnp.pad(w3q, ((0, 0), (0, Kp - K), (0, Np - N)))
    rs = jnp.pad(row_scale, ((0, 0), (0, Mp - M)))
    s1 = jnp.pad(w1s, ((0, 0), (0, Np - N)))
    s3 = jnp.pad(w3s, ((0, 0), (0, Np - N)))
    out = grouped_swiglu_q8_pallas(qp, rs, w1p, s1, w3p, s3, bm=bm2, bn=bn2,
                                   bk=bk2)
    return out[:, :M, :N]
