"""Grouped expert FFN over physical slot buffers.

Computes SwiGLU independently per physical expert slot on capacity-padded
token buffers, whose rows past each slot's count are zero.  On the TPU the
fp path runs the count-aware Pallas kernel (``kernels/grouped_gemm``): only
the filled row tiles of each slot, and no weight of a slot that holds no
row.  Other backends run the dense einsum over every row
(``grouped_ffn_ref``), which is also the tests' oracle; ``use_kernel=True``
runs the kernels on every platform (the Pallas interpreter off the TPU).

``ffn_dtype="int8"`` switches to the w8a8 path (DESIGN.md S12): activations
are quantized per token row, weights per (expert, out-feature) column over
the contraction axis, both GEMMs accumulate in int32 and dequantize at the
end (``acc * row_scale * col_scale``); the SwiGLU gate and the inter-GEMM
requantization run in fp32.  When the dispatch wire already delivered int8
slot buffers (``wire_dtype == "int8"``), the caller passes the wire codes +
scales straight in (``xs`` int8 + ``xs_scale``) and no dequant round-trip
happens between wire and compute.  It runs every row, kernel or not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import encode_int8, quantize_rows
from repro.kernels.grouped_gemm import ops as gg
from repro.kernels.grouped_gemm.ref import (
    grouped_ffn_ref,
    grouped_matmul_q8_ref,
    grouped_swiglu_q8_ref,
    stack_layer,
)

__all__ = ["grouped_ffn", "quantize_weight_cols"]


def quantize_weight_cols(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(group, out-feature) symmetric int8 over the contraction axis.

    ``w``: (G, K, N) -> (codes int8 (G, K, N), scales fp32 (G, N)).  Column
    granularity keeps the dequant a rank-1 outer product with the activation
    row scales (``acc[m, n] * a[m] * b[n]``), which the kernel applies on
    the final K step without materialising a per-element scale tensor.
    """
    scales = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1) / 127.0
    return encode_int8(w, scales[:, None, :]), scales


def _grouped_ffn_q8(xs: jax.Array, xs_scale: jax.Array, w1: jax.Array,
                    w3: jax.Array, w2: jax.Array, *,
                    use_kernel: bool) -> jax.Array:
    """w8a8 grouped SwiGLU: int8 codes in, fp32 out."""
    w1q, w1s = quantize_weight_cols(w1)
    w3q, w3s = quantize_weight_cols(w3)
    w2q, w2s = quantize_weight_cols(w2)
    if use_kernel:
        act = gg.grouped_swiglu_q8(xs, xs_scale, w1q, w1s, w3q, w3s)
        aq, as_ = quantize_rows(act)
        return gg.grouped_matmul_q8(aq, as_, w2q, w2s)
    act = grouped_swiglu_q8_ref(xs, xs_scale, w1q, w1s, w3q, w3s)
    aq, as_ = quantize_rows(act)
    return grouped_matmul_q8_ref(aq, as_, w2q, w2s)


def _slot_counts(valid: jax.Array) -> jax.Array:
    """(G,) int32 rows each slot's FFN runs: one past its last valid row.

    Dispatch packs a slot's rows to the front, so this is the slot's load;
    a row a payload screen invalidated inside that range still runs (it is
    zero, and no token reads it)."""
    rows = jnp.arange(1, valid.shape[1] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(valid, rows, 0), axis=1)


def grouped_ffn(
    xs: jax.Array,
    valid: jax.Array,
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    *,
    use_kernel: bool = False,
    ffn_dtype: str = "none",
    xs_scale: jax.Array | None = None,
    max_rows: int | None = None,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-slot SwiGLU.

    Args:
      xs: (G, C, D) capacity-padded token buffers, one per physical slot,
        zero past each slot's rows -- fp activations, or int8 wire codes on
        the end-to-end quantized path.
      valid: (G, C) bool mask of real tokens.
      w1, w3: (G, D, F); w2: (G, F, D) per-slot weights.
      use_kernel: run the Pallas kernels on every platform, not only the
        TPU (the w8a8 path's only on request).
      ffn_dtype: "none" (fp, default) or "int8" (w8a8).
      xs_scale: (G, C) fp32 per-row scales accompanying int8 ``xs``; required
        iff ``xs`` arrives already encoded.
      max_rows: static bound on the valid rows of all slots together (the
        items dispatch bucketed), which sizes the count-aware kernel's grid.
      layer: () int32 when w1/w3/w2 are whole (L, G, ...) layer stacks: the
        slots' weights are layer ``layer``'s, read in place.

    Returns:
      ``(out, rows_run)``: (G, C, D) outputs in the weight dtype, right on
      every valid row; rows past a slot's count are zero on the dense paths
      and unspecified on the count-aware one, so readers select on
      ``valid``.  ``rows_run`` (int32) counts the rows whose products were
      computed, the kernel's tile padding included.
    """
    G, C, _ = xs.shape
    if ffn_dtype == "int8":
        w1, w3, w2 = stack_layer((w1, w3, w2), layer)
        out_dtype = w1.dtype if xs.dtype == jnp.int8 else xs.dtype
        xs = jnp.where(valid[:, :, None], xs, 0)
        if xs.dtype != jnp.int8:
            xs, xs_scale = quantize_rows(xs)
        out = _grouped_ffn_q8(xs, xs_scale, w1, w3, w2, use_kernel=use_kernel)
        out = jnp.where(valid[:, :, None], out, 0).astype(out_dtype)
        return out, jnp.asarray(G * C, jnp.int32)

    def counted(xs, counts, w1, w3, w2, layer):
        return gg.grouped_ffn(xs, counts, w1, w3, w2, max_rows=max_rows,
                              layer=layer)

    def dense(xs, counts, w1, w3, w2, layer):
        out = grouped_ffn_ref(xs, *stack_layer((w1, w3, w2), layer))
        out = out.astype(xs.dtype)
        return out, jnp.asarray(G * C, jnp.int32)

    args = (xs, _slot_counts(valid), w1, w3, w2, layer)
    if use_kernel:
        return counted(*args)
    return jax.lax.platform_dependent(*args, tpu=counted, default=dense)
