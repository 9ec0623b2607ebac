"""Public grouped-GEMM ops: tile, pad to block multiples, call the kernels."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grouped_gemm.kernel import (
    grouped_ffn_pallas,
    grouped_matmul_q8_pallas,
    grouped_swiglu_q8_pallas,
)
from repro.kernels.grouped_gemm.ref import (
    grouped_ffn_ref,
    grouped_matmul_q8_ref,
    grouped_swiglu_q8_ref,
    stack_layer,
)

__all__ = ["grouped_ffn", "grouped_matmul_q8",
           "grouped_swiglu_q8"]


def _pad_to(v: int, m: int) -> int:
    return -(-v // m) * m


def _row_tile(C: int) -> int:
    """Row tile of the fp FFN kernel for slot capacity ``C``: the whole
    buffer up to 128 rows, else 128 (the MXU's height)."""
    return min(C, 128)


def _f_tile(F: int) -> int:
    """The widest of 512, 256, 128 that divides ``F``, else ``F`` whole."""
    return next((b for b in (512, 256, 128) if F % b == 0), F)


def _work_tables(counts: jax.Array, bm: int, max_work: int):
    """(slot, tile, nwork) of the filled m-tiles, slot-major.

    Work item ``w < nwork`` is m-tile ``tile[w]`` of slot ``slot[w]``; every
    entry from ``nwork`` on repeats the last real item (item 0 when there
    is none), which keeps the kernel's block indices where they were.
    """
    G = counts.shape[0]
    tiles = (counts + bm - 1) // bm
    end = jnp.cumsum(tiles)
    nwork = end[-1]
    w = jnp.minimum(jnp.arange(max_work, dtype=jnp.int32),
                    jnp.maximum(nwork - 1, 0))
    slot = jnp.minimum(jnp.searchsorted(end, w, side="right"), G - 1)
    tile = w - (end[slot] - tiles[slot])
    return (slot.astype(jnp.int32), tile.astype(jnp.int32),
            nwork.astype(jnp.int32)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _counted_ffn(bm, bf, slot, tile, nwork, layer, xs, w1, w3, w2):
    C = xs.shape[1]
    Mp = _pad_to(C, bm)
    xp = jnp.pad(xs, ((0, 0), (0, Mp - C), (0, 0))) if Mp != C else xs
    base = jnp.zeros((1,), jnp.int32)
    if layer is not None:
        # The stacks as (L * G) slots, a bitcast: slot g of the layer is
        # weight row layer * G + g, and no layer is copied out.
        base = (layer * xs.shape[0]).astype(jnp.int32)[None]
        w1, w3, w2 = (w.reshape((-1,) + w.shape[2:]) for w in (w1, w3, w2))
    out = grouped_ffn_pallas(slot, tile, nwork, base, xp, w1, w3, w2, bm=bm,
                             bf=bf)
    return out[:, :C]


def _counted_ffn_fwd(bm, bf, slot, tile, nwork, layer, xs, w1, w3, w2):
    out = _counted_ffn(bm, bf, slot, tile, nwork, layer, xs, w1, w3, w2)
    return out, (xs, w1, w3, w2, layer)


def _counted_ffn_bwd(bm, bf, res, g):
    # The dense formulation's gradients: rows past a slot's count are zero
    # on input, and the combine sends them no cotangent.
    *arrays, layer = res
    _, vjp = jax.vjp(
        lambda xs, *ws: grouped_ffn_ref(xs, *stack_layer(ws, layer)), *arrays)
    return (None, None, None, None, *vjp(g))


_counted_ffn.defvjp(_counted_ffn_fwd, _counted_ffn_bwd)


def grouped_ffn(xs: jax.Array, counts: jax.Array, w1: jax.Array,
                w3: jax.Array, w2: jax.Array, *, max_rows: int | None = None,
                layer: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Per-slot SwiGLU on the filled rows only.

    Args:
      xs: (G, C, D) slot buffers; slot g's rows ``[0, counts[g])`` are its
        tokens and the rows past them are zero.
      counts: (G,) int32 rows to run per slot, each at most C.
      w1, w3: (G, D, F); w2: (G, F, D).
      max_rows: static bound on ``counts.sum()``, which sizes the grid;
        None bounds it by ``G * C``.
      layer: () int32 when w1/w3/w2 are (L, G, ...) stacks of layers: the
        slots' weights are layer ``layer``'s, read in place.

    Returns:
      ``(out, rows_run)``: (G, C, D) outputs, right on every row of a
      filled m-tile (zero past the count there) and unwritten on the other
      tiles; and the rows run, m-tile padding included (int32).  Gradients
      are the dense formulation's (:func:`grouped_ffn_ref`).
    """
    G, C, _ = xs.shape
    bm, bf = _row_tile(C), _f_tile(w1.shape[-1])
    max_work = G * (_pad_to(C, bm) // bm)
    if max_rows is not None:
        # Each filled tile holds a row: at most max_rows of them, and at
        # most one partial tile per filled slot past max_rows // bm full.
        max_work = min(max_work, max_rows, max_rows // bm + min(G, max_rows))
    slot, tile, nwork = _work_tables(counts, bm, max(max_work, 1))
    out = _counted_ffn(bm, bf, slot, tile, nwork, layer, xs, w1, w3, w2)
    return out, nwork[0] * bm


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
