"""MoE routers: top-k gating, auxiliary losses, aux-free bias routing.

Covers the router families of the assigned architectures:

  * softmax top-k (jamba top-2/16, dbrx top-4/16, qwen/glm 8/128-160) with
    optional renormalisation of the selected weights;
  * DeepSeek-V3 sigmoid scoring with an *aux-loss-free* routing bias: the
    bias steers selection only (never the combine weights) and is updated
    outside the gradient from realized load (Wang et al., 2024);
  * GShard auxiliary load-balancing loss (Lepikhin et al., 2021);
  * a force-balanced ``ideal`` mode (the paper's upper-bound baseline) that
    assigns tokens round-robin, bypassing the learned router;
  * **rack-limited routing** (DeepSeek-V3 / Megatron-Core "node-limited"
    routing, DESIGN.md S14): each token's top-k is restricted to its
    ``rack_limit`` highest-scoring racks, bounding the number of racks a
    token's payload must reach -- and hence the inter-rack volume of the
    two-hop wire -- *at the source* instead of after the fact;
  * an **expert share**: a layer that holds one block of the router's
    experts routes over all of them and keeps only the pairs whose expert
    it holds (the chip's share of an expert-parallel deployment).

The router runs in fp32 regardless of activation dtype (routing decisions
are precision-sensitive).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["GatingConfig", "GateOut", "gate", "update_router_bias",
           "gshard_aux_loss", "rack_copy_volumes"]

_I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class GatingConfig:
    num_experts: int
    top_k: int
    score_fn: str = "softmax"          # "softmax" | "sigmoid"
    norm_topk_prob: bool = True        # renormalise selected weights to sum 1
    aux_loss_weight: float = 0.0       # GShard loss coefficient
    routed_scaling: float = 1.0        # DeepSeek-V3 scales routed output
    use_bias: bool = False             # aux-free routing bias (DeepSeek)
    bias_update_speed: float = 1e-3
    ideal: bool = False                # force-balanced round-robin router
    # Rack-limited routing (node-limited routing): each token's top-k is
    # restricted to its rack_limit best-scoring racks out of num_racks
    # expert groups (experts are rack-major: rack g owns the contiguous
    # block [g*E/G, (g+1)*E/G), matching the planner's home layout).
    # rack_limit == 0 (default) or num_racks == 1 routes freely; the masked
    # path at rack_limit == num_racks is bitwise identical to free routing.
    rack_limit: int = 0
    num_racks: int = 1
    # Rack group score = sum of the top rack_group_topk expert scores inside
    # each rack (DeepSeek-V3 uses 2); clamped to the experts per rack.
    rack_group_topk: int = 2
    # Expert share: this layer holds ``held_experts`` of the router's
    # experts from ``first_expert`` (0 = all of them).  The router keeps
    # its width and top-k; :func:`gate` maps the chosen ids into the block
    # and marks the pairs routed to experts held elsewhere as not routed
    # here (id -1, weight 0).
    held_experts: int = 0
    first_expert: int = 0

    def __post_init__(self):
        if not (0 <= self.first_expert and 0 <= self.held_experts
                and self.first_expert + self.held <= self.num_experts):
            raise ValueError(
                f"held block [{self.first_expert}, {self.first_expert} + "
                f"{self.held}) lies outside num_experts={self.num_experts}")
        if self.num_racks < 1:
            raise ValueError(f"num_racks={self.num_racks} must be >= 1")
        if not 0 <= self.rack_limit <= self.num_racks:
            raise ValueError(
                f"rack_limit={self.rack_limit} must be in "
                f"[0, num_racks={self.num_racks}]")
        if self.rack_limit > 0:
            if self.num_experts % self.num_racks != 0:
                raise ValueError(
                    f"num_experts={self.num_experts} must be a multiple of "
                    f"num_racks={self.num_racks} for rack-limited routing")
            epg = self.num_experts // self.num_racks
            if self.rack_limit * epg < self.top_k:
                raise ValueError(
                    f"rack_limit={self.rack_limit} racks expose only "
                    f"{self.rack_limit * epg} experts < top_k={self.top_k}")
        if self.rack_group_topk < 1:
            raise ValueError(
                f"rack_group_topk={self.rack_group_topk} must be >= 1")

    @property
    def held(self) -> int:
        """Experts the layer holds (the router's width unless a share)."""
        return self.held_experts or self.num_experts

    @property
    def holds_share(self) -> bool:
        """True when the layer holds only a block of the router's experts."""
        return self.held < self.num_experts

    @property
    def rack_limited(self) -> bool:
        """True when the rack-group mask path is active (may be vacuous)."""
        return self.rack_limit > 0 and self.num_racks > 1

    @property
    def rack_binding(self) -> bool:
        """True when the constraint actually binds (rack_limit < num_racks)."""
        return self.rack_limited and self.rack_limit < self.num_racks


class GateOut(NamedTuple):
    expert_ids: jax.Array     # (T, k) int32 selected logical experts; with
                              #   a share, ids within the held block and -1
                              #   for pairs routed to experts held elsewhere
    weights: jax.Array        # (T, k) combine weights (activation dtype);
                              #   0 where the id is -1
    counts: jax.Array         # (E,) int32 realized per-expert token load
                              #   over the router's whole width
    aux_loss: jax.Array       # () scalar (0 when disabled)
    scores: jax.Array         # (T, E) router probabilities (fp32)


def gshard_aux_loss(scores: jax.Array, expert_ids: jax.Array,
                    num_experts: int) -> jax.Array:
    """GShard load-balancing loss: E * sum_e f_e * P_e."""
    T = scores.shape[0]
    k = expert_ids.shape[1]
    f = jnp.zeros((num_experts,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (T * k)
    )
    p = scores.mean(axis=0)
    return num_experts * jnp.sum(f * p)


def _rack_limited_top_k(sel_scores: jax.Array, cfg: GatingConfig) -> jax.Array:
    """Group-limited top-k (DeepSeek-V3 node-limited routing).

    Per token: score each rack by the sum of its top ``rack_group_topk``
    (biased) expert scores, keep the ``rack_limit`` best racks, mask every
    other rack's experts to -inf, then take the ordinary top-k.  At
    ``rack_limit == num_racks`` the mask is all-true and ``jnp.where``
    returns ``sel_scores`` unchanged, so the selection is *bitwise* the free
    top-k -- the M = num_racks reduction property tested in
    tests/test_rack_limit.py and checked by
    :func:`repro.analysis.plan_check.verify_rack_limit`.

    This is the single sanctioned selection site: the ``rack-limit`` lint
    rule flags any other ``top_k`` over expert scores under ``moe/``.
    """
    T, E = sel_scores.shape
    G, M = cfg.num_racks, cfg.rack_limit
    epg = E // G
    gk = min(cfg.rack_group_topk, epg)
    grp_scores, _ = jax.lax.top_k(sel_scores.reshape(T, G, epg), gk)
    _, top_racks = jax.lax.top_k(grp_scores.sum(axis=-1), M)     # (T, M)
    rack_mask = jnp.any(
        top_racks[:, :, None] == jnp.arange(G, dtype=top_racks.dtype),
        axis=1)                                                  # (T, G)
    masked = jnp.where(jnp.repeat(rack_mask, epg, axis=-1),
                       sel_scores, -jnp.inf)
    _, expert_ids = jax.lax.top_k(masked, cfg.top_k)
    return expert_ids.astype(_I32)


def rack_copy_volumes(
    expert_ids: jax.Array,
    home: jax.Array,
    *,
    num_ranks: int,
    rack_size: int,
    src_rank: jax.Array,
) -> jax.Array:
    """(3,) int32 *deduplicated* at-gate payload copies by fabric tier.

    A fabric that aggregates dispatch per destination (the two-hop wire's
    design point, and the reason DeepSeek-V3 limits tokens to M nodes) must
    move each token's payload once per distinct destination, not once per
    (token, expert) item: a token selecting several experts homed on the
    same rank/rack crosses the wire a single time and fans out at the far
    end.  This is the quantity ``rack_limit`` bounds structurally -- at most
    M inter-rack copies per token -- whereas the item count is untouched by
    the mask.  Returned as [local, intra_rack, inter_rack] where local =
    copies staying on ``src_rank``, intra = distinct other ranks inside the
    source rack, inter = distinct destination *racks* outside it (the
    aggregated hop-1 volume of the two-hop wire).

    Computed against the *home* placement -- the plan-independent at-gate
    view; the planner's reroute may only move volume between tiers from
    here (``Plan.tier_tokens`` is the post-plan twin, in items).
    """
    dst_rank = home.astype(_I32)[expert_ids]                     # (T, k)
    sent = jnp.any(
        dst_rank[:, :, None] == jnp.arange(num_ranks, dtype=_I32),
        axis=1)                                                  # (T, R)
    ranks = jnp.arange(num_ranks, dtype=_I32)
    same_rank = ranks == src_rank
    same_rack = (ranks // rack_size) == (src_rank // rack_size)
    local = jnp.sum(sent & same_rank)
    intra = jnp.sum(sent & same_rack & ~same_rank)
    # Inter-rack copies are deduplicated per destination *rack*: hop 1 of
    # the two-hop wire carries one aggregated payload per (token, rack).
    rack_sent = jnp.any(
        ((dst_rank // rack_size)[:, :, None]
         == jnp.arange(num_ranks // rack_size, dtype=_I32)), axis=1)
    inter = jnp.sum(
        rack_sent
        & (jnp.arange(num_ranks // rack_size, dtype=_I32)
           != src_rank // rack_size))
    return jnp.stack([local, intra, inter]).astype(_I32)


def gate(
    x: jax.Array,
    w_router: jax.Array,
    cfg: GatingConfig,
    *,
    bias: jax.Array | None = None,
) -> GateOut:
    """Route tokens.

    Args:
      x: (T, D) token activations.
      w_router: (D, E) router projection.
      cfg: gating configuration.
      bias: (E,) aux-free selection bias (DeepSeek), ignored unless
        ``cfg.use_bias``.
    """
    T = x.shape[0]
    E, k = cfg.num_experts, cfg.top_k
    logits = jnp.asarray(x, jnp.float32) @ jnp.asarray(w_router, jnp.float32)

    if cfg.score_fn == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif cfg.score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown score_fn {cfg.score_fn}")

    if cfg.ideal:
        # Force-balanced upper bound: round-robin over experts; weights from
        # the learned scores so magnitudes remain realistic.
        base = (jnp.arange(T, dtype=_I32) * k) % E
        expert_ids = (base[:, None] + jnp.arange(k, dtype=_I32)[None, :]) % E
        sel = jnp.take_along_axis(scores, expert_ids, axis=1)
    else:
        sel_scores = scores
        if cfg.use_bias and bias is not None:
            # The bias steers *selection only*; stop_gradient makes that a
            # structural guarantee rather than an accident of top_k being
            # non-differentiable (the combine weights below re-gather from
            # the unbiased scores, so no gradient may ever reach the bias).
            sel_scores = scores + jax.lax.stop_gradient(
                bias[None, :].astype(jnp.float32))
        if cfg.rack_limited:
            expert_ids = _rack_limited_top_k(sel_scores, cfg)
        else:
            _, expert_ids = jax.lax.top_k(sel_scores, k)
            expert_ids = expert_ids.astype(_I32)
        # Combine weights always come from the *unbiased* scores.
        sel = jnp.take_along_axis(scores, expert_ids, axis=1)

    if cfg.norm_topk_prob:
        sel = sel / jnp.maximum(sel.sum(axis=-1, keepdims=True), 1e-20)
    sel = sel * cfg.routed_scaling

    counts = jnp.zeros((E,), _I32).at[expert_ids.reshape(-1)].add(1)
    aux = jnp.zeros((), jnp.float32)
    if cfg.aux_loss_weight > 0.0:
        aux = cfg.aux_loss_weight * gshard_aux_loss(scores, expert_ids, E)
    if cfg.holds_share:
        # Top-k and renormalisation ran over the whole router; a pair whose
        # expert lies outside the held block is not routed here.
        local = expert_ids - cfg.first_expert
        here = (local >= 0) & (local < cfg.held)
        expert_ids = jnp.where(here, local, -1)
        sel = jnp.where(here, sel, 0)
    return GateOut(expert_ids, sel.astype(x.dtype), counts, aux, scores)


def update_router_bias(bias: jax.Array, counts: jax.Array,
                       speed: float, *, num_racks: int = 1) -> jax.Array:
    """Aux-free bias update: nudge under-loaded experts up, overloaded down.

    Applied outside the gradient once per (global) batch, DeepSeek-V3 style.

    ``num_racks > 1`` is the two-level per-rack variant for rack-limited
    routing.  It splits the error the way the masked router splits the
    decision:

    * within-rack term (half gain) -- each expert vs its *own rack group's*
      mean load.  This is the only pressure the mask lets act freely: once
      a token has picked its racks, bias differences inside a group reorder
      the restricted top-k.  Half gain because the score gaps inside a
      restricted top-k are small -- a full-speed sign step dithers harder
      than it corrects.
    * rack-steering term (full gain) -- each rack group's mean load vs the
      global mean, applied *uniformly* to every expert of the group.  A
      uniform offset cannot reorder experts within the rack, but the
      rack-choice group score sums *biased* scores, so an under-loaded
      rack's group score rises and the mask itself is steered toward it.
      Without this term the group-score signal stays popularity-driven and
      no amount of within-rack centering can fix cross-rack imbalance.

    ``num_racks == 1`` takes the global branch unchanged (bitwise the
    pre-rack-limit update).
    """
    load = counts.astype(jnp.float32)
    if num_racks > 1:
        E = load.shape[0]
        if E % num_racks != 0:
            raise ValueError(
                f"num_experts={E} must be a multiple of num_racks="
                f"{num_racks}")
        rack_mean = jnp.repeat(load.reshape(num_racks, -1).mean(axis=1),
                               E // num_racks)
        err = rack_mean - load          # within-rack: reorder the top-k
        steer = load.mean() - rack_mean  # rack-steering: move the mask
        return bias + speed * (0.5 * jnp.sign(err) + jnp.sign(steer))
    err = load.mean() - load            # >0 for under-loaded experts
    return bias + speed * jnp.sign(err)
