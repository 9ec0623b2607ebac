"""Plain float32 reference of a MoE language model whose chip holds one
block of each layer's experts (an expert share), and its control.

The chip's share of an expert-parallel deployment: the router keeps its
published width and experts per token, softmax or sigmoid scores over all
of them, the top-k weights renormalised over the k chosen among all, and
only the experts of the held block add their part; what the experts held
on other chips would add is left out, as on the chip.  The configuration
file's ``num_experts`` counts the held experts, ``reduced.num_experts``
the router's published width, and ``deployment`` gives the block.
Everything else is :class:`bench.reference.Reference`: it imports nothing
of the program and draws each weight from the seed by its role, the
experts as the block's own ``(held, D, F)`` leaves.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import BLOCK_E, Reference, _mm, _swiglu, served_gaps

__all__ = ["ShareReference", "served_gaps", "block_of"]


def block_of(c: dict) -> tuple[int, int, int]:
    """(router width, first held expert, experts held) of a configuration."""
    d = c["deployment"]
    return c["reduced"]["num_experts"], d["first_expert"], d["experts_held"]


class ShareReference(Reference):
    """The reference model of one configuration file with a held block."""

    def __init__(self, c: dict, seed: int):
        super().__init__(c, seed)
        self.E_router, self.first, self.E = block_of(c)
        if c["num_experts"] != self.E:
            raise ValueError("num_experts must count the experts held")
        self.key = "share:" + self.key

    def layer_weights(self, l: int) -> dict:
        if l >= self.first_dense:
            # Drawn at the router's width first: the base class then finds
            # it among the drawn weights, by its role.
            self._get(f"layer{l}.moe.router", (self.D, self.E_router),
                      jnp.float32)
        return super().layer_weights(l)

    def _ffn(self, w, h, prec):
        if "f1" in w:                     # a leading dense layer
            return super()._ffn(w, h, prec)
        c = self.c
        logits = _mm("td,de->te", h, w["router"], prec)
        scores = (jax.nn.sigmoid(logits) if c["program"]["score_fn"]
                  == "sigmoid" else jax.nn.softmax(logits, axis=-1))
        top, ids = jax.lax.top_k(scores, c["num_experts_per_tok"])
        if c["norm_topk_prob"]:
            top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-20)
        top = top * c.get("routed_scaling_factor", 1.0)
        gate = jnp.zeros_like(scores).at[
            jnp.arange(h.shape[0])[:, None], ids].set(top)      # (T, E)
        gate = gate[:, self.first:self.first + self.E]          # held block

        def block(args):
            w1, w3, w2, g = args          # (Eb, D, F), ..., (T, Eb)
            a = (jax.nn.silu(_mm("td,edf->etf", h, w1, prec))
                 * _mm("td,edf->etf", h, w3, prec))
            y = _mm("etf,efd->etd", a, w2, prec)
            return jnp.einsum("te,etd->td", g, y,
                              precision=jax.lax.Precision.HIGHEST)

        be = math.gcd(BLOCK_E, self.E)
        nb = self.E // be
        blocks = (w["w1"].reshape(nb, be, *w["w1"].shape[1:]),
                  w["w3"].reshape(nb, be, *w["w3"].shape[1:]),
                  w["w2"].reshape(nb, be, *w["w2"].shape[1:]),
                  jnp.moveaxis(gate.reshape(-1, nb, be), 1, 0))
        y = jax.lax.map(block, blocks).sum(0)
        if "sw1" in w:
            y = y + _swiglu(h, w["sw1"], w["sw3"], w["sw2"], prec)
        return y
