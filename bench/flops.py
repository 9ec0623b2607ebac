"""Operations the model requires, counted from the configuration's shapes.

Counted per token of the forward pass: the attention projections, the
causal attention scores (``q k^T`` and the weighted sum of values, over
the positions the token attends), the router, the top-k routed experts,
the shared experts, the dense FFN and the unembedding.  A multiply-add
is 2 operations.  Capacity padding, padded tokens and recomputation are
not counted: they are work the program chose, not work the model needs.
"""

from __future__ import annotations

__all__ = ["Dims", "dims", "linear_flops", "attn_flops", "span_flops"]

from typing import NamedTuple


class Dims(NamedTuple):
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    dense_ff: int
    experts: int
    top_k: int
    expert_ff: int
    shared_ff: int          # all shared experts together
    kinds: tuple            # "dense" | "moe" per layer


def dims(c: dict) -> Dims:
    """Shapes from a configuration file's published keys."""
    n = c["num_hidden_layers"]
    first_dense = c.get("first_k_dense_replace", 0)
    experts = c.get("n_routed_experts", c.get("num_experts", 0))
    kinds = tuple("dense" if (i < first_dense or not experts) else "moe"
                  for i in range(n))
    return Dims(
        d_model=c["hidden_size"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        vocab=c["vocab_size"], dense_ff=c["intermediate_size"],
        experts=experts, top_k=c.get("num_experts_per_tok", 0),
        expert_ff=c.get("moe_intermediate_size", 0),
        shared_ff=c.get("n_shared_experts", 0)
        * c.get("moe_intermediate_size", 0),
        kinds=kinds)


def linear_flops(d: Dims, *, head: bool = True) -> int:
    """Forward operations of one token outside the attention scores."""
    D = d.d_model
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    attn = 2 * D * (q + 2 * kv) + 2 * q * D
    per_kind = {
        "dense": 3 * 2 * D * d.dense_ff,
        "moe": (2 * D * d.experts + d.top_k * 3 * 2 * D * d.expert_ff
                + 3 * 2 * D * d.shared_ff),
    }
    total = sum(attn + per_kind[k] for k in d.kinds)
    return total + (2 * D * d.vocab if head else 0)


def attn_flops(d: Dims, start: int, n: int) -> int:
    """Score operations of ``n`` tokens at positions ``start..start+n-1``,
    each attending itself and every earlier position, over all layers."""
    attended = n * (start + 1) + n * (n - 1) // 2      # sum of (p + 1)
    return len(d.kinds) * 4 * d.heads * d.head_dim * attended


def span_flops(d: Dims, start: int, n: int, *, head_tokens: int) -> int:
    """Forward operations of ``n`` consecutive tokens from ``start``, of
    which ``head_tokens`` need logits."""
    return ((n - head_tokens) * linear_flops(d, head=False)
            + head_tokens * linear_flops(d) + attn_flops(d, start, n))

