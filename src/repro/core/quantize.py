"""Reusable int8/bf16 quantization primitives for wire and compute.

One quantization story spans three payload paths (DESIGN.md S12):

* the **hop-1 token payload** of the two-hop dispatch wire
  (:mod:`repro.moe.stages` quantizes before the inter-rack hop, carries the
  fp32 scales bitcast *inside* the int8 payload, and dequantizes after the
  intra-rack scatter);
* the **replica weight stream** (:mod:`repro.moe.distribute` encodes each
  expert's weights once at the home rank; the tiered reduce-scatter stays
  exact because every slot has exactly one nonzero contribution and all-zero
  rows encode to scale 0);
* the **expert FFN** itself (w8a8 grouped SwiGLU,
  :mod:`repro.kernels.grouped_gemm`), so an int8 wire can feed the int8
  kernel without a dequant round-trip.

The scheme everywhere is per-row-group *symmetric* int8 with fp32 scales:
``scale = amax(|row|) / 127`` (exactly 0 for all-zero rows -- the property
the replica-stream reduce relies on), ``q = clip(round(x / scale))`` with
a safe divide.  Rounding is round-to-nearest by default; pass a PRNG key
for stochastic rounding (unbiased in expectation -- the right choice when a
*gradient* payload is quantized without error feedback).  Activations use
plain nearest rounding and **no error feedback**: there is no "next step"
to carry an activation residual into, and feedback across unrelated tokens
would inject one token's error into another (DESIGN.md S12).

:mod:`repro.optim.grad_compress` layers error feedback for the cross-pod
gradient all-reduce on top of the same primitives.

The byte-accounting helpers at the bottom are pure Python (no jax) so the
host-side cost model (:mod:`repro.core.comm_plan`) and the static verifier
(:mod:`repro.analysis.plan_check`) can share one definition of "payload
width" with the device code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "WIRE_DTYPES",
    "FFN_DTYPES",
    "tensor_scale",
    "encode_int8",
    "decode_int8",
    "quantize_rows",
    "dequantize_rows",
    "encode_wire",
    "decode_wire",
    "split_wire_int8",
    "payload_bytes_per_item",
    "expert_wire_bytes",
    "wire_dtype_bytes",
]

# "none" carries the payload at its native dtype (the bit-exact oracle
# path); "bf16" halves it; "int8" quarters it (+ 4 scale bytes per row).
WIRE_DTYPES = ("none", "bf16", "int8")
FFN_DTYPES = ("none", "int8")

_SCALE_BYTES = 4  # one fp32 scale per quantization row


# --------------------------------------------------------------------------
# Core int8 primitives (shared by wire, replica stream, FFN, grad compress)
# --------------------------------------------------------------------------


def tensor_scale(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Per-tensor symmetric scale ``max(amax(|x|), eps) / 127``.

    The eps floor keeps the gradient-compression path (which divides by the
    scale unconditionally) well-defined on all-zero tensors; row-wise wire
    encoding uses :func:`quantize_rows` instead, whose scales are *exactly*
    zero on zero rows.
    """
    return jnp.maximum(jnp.max(jnp.abs(x)), eps) / 127.0


def encode_int8(x: jax.Array, scale: jax.Array,
                key: jax.Array | None = None) -> jax.Array:
    """``clip(round(x / scale), -127, 127)`` as int8, safe at ``scale == 0``.

    ``scale`` broadcasts against ``x`` (scalar for per-tensor, ``(..., 1)``
    for per-row).  With ``key``, rounding is stochastic: ``floor(v + u)``
    with ``u ~ U[0, 1)``, which is unbiased in expectation -- use it when
    quantizing gradients without error feedback; activations default to
    round-to-nearest (no feedback path exists for them, module docstring).
    """
    v = jnp.where(scale > 0, x.astype(jnp.float32) / jnp.where(scale > 0,
                                                               scale, 1.0), 0)
    if key is None:
        v = jnp.round(v)
    else:
        v = jnp.floor(v + jax.random.uniform(key, v.shape, jnp.float32))
    return jnp.clip(v, -127, 127).astype(jnp.int8)


def decode_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`encode_int8` (fp32)."""
    return q.astype(jnp.float32) * scale


def quantize_rows(x: jax.Array, key: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 over the last axis.

    Returns ``(q, scales)`` with ``q`` int8 of ``x.shape`` and ``scales``
    fp32 of ``x.shape[:-1]``.  All-zero rows get scale exactly 0 and decode
    to exact zeros -- the invariant the replica-stream reduce-scatter needs
    (one nonzero contribution per slot sums exactly).
    """
    scales = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    return encode_int8(x, scales[..., None], key=key), scales


def dequantize_rows(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_rows` (fp32)."""
    return decode_int8(q, scales[..., None])


# --------------------------------------------------------------------------
# Wire codec: the scales travel *inside* the int8 payload
# --------------------------------------------------------------------------


def encode_wire(x: jax.Array, wire_dtype: str) -> jax.Array:
    """Encode a ``(..., D)`` payload for the EP wire.

    ``"none"`` is the identity (bit-exact oracle path).  ``"bf16"`` casts.
    ``"int8"`` quantizes each ``D``-row and packs its fp32 scale bitcast
    into 4 trailing int8 lanes, returning ``(..., D + 4)`` int8 -- ONE
    buffer rides the (possibly two-hop) all_to_all, so scales take the
    exact same path as the rows they describe and per-tier byte accounting
    is simply ``items * (D + 4)``.
    """
    if wire_dtype == "none":
        return x
    if wire_dtype == "bf16":
        return x.astype(jnp.bfloat16)
    if wire_dtype != "int8":
        raise ValueError(f"unknown wire_dtype: {wire_dtype!r}")
    q, scales = quantize_rows(x)
    packed = jax.lax.bitcast_convert_type(scales, jnp.int8)  # (..., 4)
    return jnp.concatenate([q, packed], axis=-1)


def decode_wire(buf: jax.Array, wire_dtype: str, out_dtype) -> jax.Array:
    """Inverse of :func:`encode_wire`; returns ``(..., D)`` in ``out_dtype``."""
    if wire_dtype == "none":
        return buf
    if wire_dtype == "bf16":
        return buf.astype(out_dtype)
    if wire_dtype != "int8":
        raise ValueError(f"unknown wire_dtype: {wire_dtype!r}")
    q, packed = buf[..., :-_SCALE_BYTES], buf[..., -_SCALE_BYTES:]
    scales = jax.lax.bitcast_convert_type(packed, jnp.float32)
    return dequantize_rows(q, scales).astype(out_dtype)


def split_wire_int8(buf: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split an int8 wire buffer into ``(q, scales)`` WITHOUT dequantizing.

    The end-to-end quantized path (``wire_dtype == ffn_dtype == "int8"``)
    feeds the slot buffers straight into the w8a8 grouped kernel; this is
    the seam that avoids the dequant round-trip.
    """
    q, packed = buf[..., :-_SCALE_BYTES], buf[..., -_SCALE_BYTES:]
    return q, jax.lax.bitcast_convert_type(packed, jnp.float32)


# --------------------------------------------------------------------------
# Byte accounting (pure Python -- shared by cost model and verifier)
# --------------------------------------------------------------------------


def wire_dtype_bytes(wire_dtype: str, base_bytes: int = 4) -> int:
    """Per-element payload width in bytes (excluding scale overhead)."""
    if wire_dtype == "none":
        return base_bytes
    if wire_dtype == "bf16":
        return 2
    if wire_dtype == "int8":
        return 1
    raise ValueError(f"unknown wire_dtype: {wire_dtype!r}")


def payload_bytes_per_item(d_model: int, wire_dtype: str,
                           base_bytes: int = 4) -> int:
    """Wire bytes of ONE routed token item, scale overhead included.

    ``"int8"`` carries one fp32 scale per token row (packed in-band by
    :func:`encode_wire`), so the item costs ``d_model + 4`` bytes.
    """
    n = d_model * wire_dtype_bytes(wire_dtype, base_bytes)
    return n + (_SCALE_BYTES if wire_dtype == "int8" else 0)


def expert_wire_bytes(d_model: int, d_ff: int, wire_dtype: str,
                      base_bytes: int = 4) -> int:
    """Wire bytes of one expert's (w1, w3, w2) replica-stream payload.

    w1/w3 are (D, F) quantized per D-row, w2 is (F, D) quantized per F-row:
    ``3*D*F`` elements plus ``2*D + F`` fp32 scales for int8.
    """
    n = 3 * d_model * d_ff * wire_dtype_bytes(wire_dtype, base_bytes)
    if wire_dtype == "int8":
        n += (2 * d_model + d_ff) * _SCALE_BYTES
    return n
