"""Fused gating Pallas kernel: score + iterative top-k + expert histogram.

TPU has no native top-k; the standard kernel strategy for small k (<=8 on
every assigned arch) is k rounds of (max, argmax, mask) over the expert
axis, fused with the score activation and the per-expert count histogram so
the (T, E) score matrix is read once from VMEM instead of three times
(softmax -> topk -> histogram ).  This feeds the load matrix Lambda that
UltraEP's planner consumes -- it is the "notify" half of dispatch.

Grid: (T/bt,).  Blocks: logits (bt, E); outputs ids/weights (bt, k) and a
per-block partial histogram (E,) summed by XLA afterwards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gating_topk_pallas"]


def _kernel(logit_ref, ids_ref, w_ref, cnt_ref, *, k: int, score_fn: str,
            E: int, bt: int):
    x = logit_ref[...].astype(jnp.float32)              # (bt, E)
    if score_fn == "softmax":
        m = x.max(axis=1, keepdims=True)
        ex = jnp.exp(x - m)
        scores = ex / ex.sum(axis=1, keepdims=True)
    else:
        scores = jax.nn.sigmoid(x)

    cnt = jnp.zeros((E,), jnp.int32)
    s = scores
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    for i in range(k):
        w = s.max(axis=1)
        a = jnp.argmax(s, axis=1).astype(jnp.int32)
        ids_ref[:, i] = a
        w_ref[:, i] = w
        hit = cols == a[:, None]
        cnt = cnt + hit.astype(jnp.int32).sum(axis=0)
        s = jnp.where(hit, -jnp.inf, s)
    cnt_ref[...] = cnt[None, :]


@functools.partial(jax.jit, static_argnames=("k", "score_fn", "bt"))
def gating_topk_pallas(logits: jax.Array, k: int, *, score_fn: str = "softmax",
                       bt: int = 1024):
    """logits: (T, E).  Returns (ids, weights, counts)."""
    T, E = logits.shape
    bt = min(bt, T)
    if T % bt:
        raise ValueError(f"T={T} not divisible by bt={bt}")
    grid = (T // bt,)
    ids, w, cnt = pl.pallas_call(
        functools.partial(_kernel, k=k, score_fn=score_fn, E=E, bt=bt),
        grid=grid,
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((T, k), jnp.float32),
            jax.ShapeDtypeStruct((T // bt, E), jnp.int32),
        ],
    )(logits)
    return ids, w, cnt.sum(axis=0)
