"""Public fused-gating op: pick a block that divides T, call the kernel."""

from __future__ import annotations

import jax

from repro.kernels.gating_topk.kernel import gating_topk_pallas

__all__ = ["gating_topk"]


def gating_topk(logits: jax.Array, k: int, *, score_fn: str = "softmax",
                bt: int = 1024):
    T = logits.shape[0]
    # choose a divisor block
    bt = min(bt, T)
    while T % bt:
        bt -= 1
    return gating_topk_pallas(logits, k, score_fn=score_fn, bt=bt)
