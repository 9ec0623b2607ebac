"""Multi-sort EP token dispatch/combine: the oracle of the fused engine.

The production layer dispatches with the single-sort engine of
:mod:`repro.moe.permute`; this module keeps the multi-sort scatter path it
replaced, and :func:`moe_layer_reference` runs a whole layer on it so tests
can hold the engine to it bit for bit (DESIGN.md S2).  Per rank, inside
``shard_map`` over the EP ("model") axis:

  1. gate locally, all_gather per-expert counts -> exact load matrix Lambda;
  2. solve the balancing plan (identical on every rank, zero extra sync --
     the paper's "deterministically computes an identical plan");
  3. reroute: per-item destination rank via cumulative-quota lookup;
  4. dispatch: scatter items into fixed-capacity per-destination buffers and
     ``all_to_all`` them across the EP group;
  5. bucket received items into per-physical-slot buffers, grouped FFN;
  6. inverse path: results return in the same buffer positions, so the
     combine is a gather + weighted sum with no extra metadata exchange
     (the paper's "scatter-to-gather inversion").

Static shapes: ``cap_pair`` bounds tokens per (src, dst) pair and
``cap_slot`` bounds tokens per physical expert slot.  Overflow is dropped
and *counted* (exposed in stats); equivalence tests run with capacities
sized for zero drops.  Balancing is precisely what makes small capacities
safe -- the measured max slot occupancy under each balancer mode is the
paper's Fig. 14 activation-memory story.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.planner import occurrence_index, token_targets
from repro.moe import stages
from repro.moe.expert import grouped_ffn
from repro.moe.reference import swiglu

__all__ = ["DispatchOut", "dispatch_tokens", "combine_tokens", "bucket_by_slot",
           "unbucket", "moe_layer_reference"]

_I32 = jnp.int32


class DispatchOut(NamedTuple):
    send_x: jax.Array        # (R, cap_pair, D) padded send buffers
    send_e: jax.Array        # (R, cap_pair) logical expert ids, -1 pad
    item_dst: jax.Array      # (T*k,) destination rank per item (-1 dropped)
    item_pos: jax.Array      # (T*k,) position within (dst) buffer
    item_kept: jax.Array     # (T*k,) bool
    drops: jax.Array         # () int32 dropped items on this rank


def dispatch_tokens(
    x_local: jax.Array,
    expert_ids: jax.Array,
    q_row: jax.Array,
    *,
    cap_pair: int,
) -> DispatchOut:
    """Build per-destination send buffers from the plan's reroute split.

    Args:
      x_local: (T, D) local tokens.
      expert_ids: (T, k) selected logical experts.
      q_row: (E, R) this source rank's reroute split (plan.q[my_rank]).
      cap_pair: static capacity per (src, dst) pair.
    """
    T, k = expert_ids.shape
    D = x_local.shape[-1]
    R = q_row.shape[-1]
    items_e = expert_ids.reshape(-1)                     # (T*k,)
    items_t = jnp.repeat(jnp.arange(T, dtype=_I32), k)   # token of each item

    dst = token_targets(items_e, q_row)                  # (T*k,)
    pos = occurrence_index(dst)                          # j-th item to dst
    kept = pos < cap_pair
    drops = jnp.sum(~kept).astype(_I32)

    safe_dst = jnp.where(kept, dst, 0)
    safe_pos = jnp.where(kept, pos, 0)
    send_x = jnp.zeros((R, cap_pair, D), x_local.dtype)
    send_e = jnp.full((R, cap_pair), -1, _I32)
    # Scatter items; dropped items overwrite slot (0,0) harmlessly below via
    # masking: scatter only kept items by routing drops to a scratch row.
    scratch_dst = jnp.where(kept, safe_dst, R - 1)
    scratch_pos = jnp.where(kept, safe_pos, cap_pair - 1)
    # To avoid clobbering real data with dropped items, apply kept as weight.
    send_x = send_x.at[scratch_dst, scratch_pos].add(
        x_local[items_t] * kept[:, None].astype(x_local.dtype)
    )
    send_e = send_e.at[scratch_dst, scratch_pos].max(
        jnp.where(kept, items_e, -1)
    )
    return DispatchOut(send_x, send_e, jnp.where(kept, dst, -1), pos, kept, drops)


def bucket_by_slot(
    recv_x: jax.Array,
    recv_e: jax.Array,
    slot_of: jax.Array,
    *,
    num_slots: int,
    cap_slot: int,
):
    """Group received items into per-physical-slot capacity buffers.

    Args:
      recv_x: (R, cap_pair, D) received tokens.
      recv_e: (R, cap_pair) logical expert per token (-1 pad).
      slot_of: (E,) local physical slot of each logical expert (-1 if not
        hosted here; such items are dropped -- they indicate a plan bug and
        are counted).

    Returns:
      (xs, valid, back_idx, drops): slot buffers (num_slots, cap_slot, D),
      their validity mask, and for each buffer entry the flat index into the
      (R*cap_pair) receive stream it came from (for the inverse scatter).
    """
    R, cap_pair, D = recv_x.shape
    flat_x = recv_x.reshape(-1, D)
    flat_e = recv_e.reshape(-1)
    is_real = flat_e >= 0
    slot = jnp.where(is_real, slot_of[jnp.where(is_real, flat_e, 0)], num_slots)
    hosted_ok = slot >= 0
    slot = jnp.where(hosted_ok, slot, num_slots)  # park bad items past the end

    pos = occurrence_index(slot.astype(_I32))
    kept = (slot < num_slots) & (pos < cap_slot)
    drops = jnp.sum(is_real & ~kept).astype(_I32)

    safe_slot = jnp.where(kept, slot, num_slots - 1).astype(_I32)
    safe_pos = jnp.where(kept, pos, cap_slot - 1)
    xs = jnp.zeros((num_slots, cap_slot, D), recv_x.dtype)
    xs = xs.at[safe_slot, safe_pos].add(
        flat_x * kept[:, None].astype(flat_x.dtype)
    )
    valid = jnp.zeros((num_slots, cap_slot), jnp.bool_)
    valid = valid.at[safe_slot, safe_pos].max(kept)
    back_idx = jnp.full((num_slots, cap_slot), -1, _I32)
    back_idx = back_idx.at[safe_slot, safe_pos].max(
        jnp.where(kept, jnp.arange(flat_e.shape[0], dtype=_I32), -1)
    )
    return xs, valid, back_idx, drops


def unbucket(
    out: jax.Array,
    valid: jax.Array,
    back_idx: jax.Array,
    recv_shape: tuple[int, int, int],
) -> jax.Array:
    """Scatter slot-buffer outputs back into the (R, cap_pair, D) layout."""
    R, cap_pair, D = recv_shape
    flat = jnp.zeros((R * cap_pair, D), out.dtype)
    idx = jnp.where(valid, back_idx, 0).reshape(-1)
    vals = (out * valid[:, :, None].astype(out.dtype)).reshape(-1, D)
    flat = flat.at[idx].add(vals)
    return flat.reshape(R, cap_pair, D)


def combine_tokens(
    ret_x: jax.Array,
    disp: DispatchOut,
    weights: jax.Array,
    num_tokens: int,
) -> jax.Array:
    """Weighted combine of returned expert outputs back onto source tokens.

    Args:
      ret_x: (R, cap_pair, D) expert outputs returned via the inverse
        all_to_all, in the same positions the items were sent from.
      disp: the DispatchOut of the forward dispatch.
      weights: (T, k) combine weights.
      num_tokens: T.
    """
    T, k = weights.shape
    D = ret_x.shape[-1]
    items_t = jnp.repeat(jnp.arange(T, dtype=_I32), k)
    flat_w = weights.reshape(-1)
    safe_dst = jnp.where(disp.item_kept, disp.item_dst, 0)
    safe_pos = jnp.where(disp.item_kept, disp.item_pos, 0)
    vals = ret_x[safe_dst, safe_pos] * (
        flat_w * disp.item_kept.astype(flat_w.dtype)
    )[:, None].astype(ret_x.dtype)
    y = jnp.zeros((num_tokens, D), ret_x.dtype)
    return y.at[items_t].add(vals)


def moe_layer_reference(x: jax.Array, params, cfg, *,
                        axis_name: str | None
                        ) -> tuple[jax.Array, jax.Array, stages.MoEStats]:
    """One MoE layer on the multi-sort path (call under shard_map).

    Runs the production gate, plan and distribute stages, then dispatches
    with :func:`dispatch_tokens` -> all_to_all -> :func:`bucket_by_slot`
    (replicated: each rank keeps the items ``token_targets`` gives it under
    the one-source split ``u``), applies the grouped FFN to the main and
    replica slot ranges, and returns the outputs through :func:`unbucket` ->
    all_to_all -> :func:`combine_tokens`.  Same signature and results as
    :func:`repro.moe.layer.moe_layer_local`, for flat EP only: unchunked,
    with no wire codec and no expert share.
    """
    if (cfg.dispatch_mode not in ("a2a", "replicated") or cfg.racks != 1
            or cfg.overlap_chunks != 1 or cfg.wire_dtype != "none"
            or cfg.gating.holds_share):
        raise ValueError(
            "moe_layer_reference models flat EP (dispatch_mode 'a2a' or "
            "'replicated', racks=1), unchunked (overlap_chunks=1), with no "
            "wire codec (wire_dtype='none') and no expert share")
    ctx = stages.make_stage_ctx(cfg, axis_name)
    gs = stages.gate_stage(ctx, x, params.router)
    ps = stages.plan_stage(ctx, gs)
    dist = stages.distribute_stage(ctx, params, gs, ps)
    expert_ids, weights = gs.gate_out.expert_ids, gs.gate_out.weights
    num_slots = cfg.layout.slots_per_rank
    slot_of = ps.slot_of_all[gs.my]                        # (E,)
    T, D = x.shape

    if cfg.dispatch_mode == "replicated":
        items_e = expert_ids.reshape(-1)
        # (T*k,): u is the one-source split.
        owner = token_targets(items_e, ps.plan.u)
        mine = owner == gs.my
        recv_e = jnp.where(mine, items_e, -1)[None, :]       # (1, T*k)
        recv_x = jnp.repeat(x, cfg.gating.top_k, axis=0)[None, :, :]
        drops_dispatch = jnp.zeros((), _I32)
    else:
        q_row = ps.plan.q[gs.my]                           # (E, R)
        disp = dispatch_tokens(x, expert_ids, q_row, cap_pair=cfg.cap_pair)
        if axis_name is not None:
            recv_x = jax.lax.all_to_all(disp.send_x, axis_name, 0, 0,
                                        tiled=False)
            recv_e = jax.lax.all_to_all(disp.send_e, axis_name, 0, 0,
                                        tiled=False)
        else:
            recv_x, recv_e = disp.send_x, disp.send_e
        drops_dispatch = disp.drops
    xs, valid, back_idx, drops_slot = bucket_by_slot(
        recv_x, recv_e, slot_of, num_slots=num_slots, cap_slot=cfg.cap_slot)

    n_main = dist.w1.shape[0]
    out = jnp.concatenate([
        grouped_ffn(xs[:n_main], valid[:n_main], dist.w1, dist.w3, dist.w2,
                    use_kernel=cfg.use_kernel, ffn_dtype=cfg.ffn_dtype)[0],
        grouped_ffn(xs[n_main:], valid[n_main:], dist.w1r, dist.w3r,
                    dist.w2r, use_kernel=cfg.use_kernel,
                    ffn_dtype=cfg.ffn_dtype)[0],
    ], axis=0)

    if cfg.dispatch_mode == "replicated":
        k = weights.shape[1]
        ret = unbucket(out, valid, back_idx, (1, T * k, D))
        items_t = jnp.repeat(jnp.arange(T, dtype=_I32), k)
        vals = ret[0] * weights.reshape(-1)[:, None].astype(ret.dtype)
        y = jnp.zeros((T, D), ret.dtype).at[items_t].add(vals)
        if axis_name is not None:
            y = jax.lax.psum(y, axis_name)
    else:
        ret = unbucket(out, valid, back_idx, (cfg.ep_size, cfg.cap_pair, D))
        if axis_name is not None:
            ret = jax.lax.all_to_all(ret, axis_name, 0, 0, tiled=False)
        y = combine_tokens(ret, disp, weights, T)

    if cfg.n_shared_experts > 0:
        y = y + swiglu(x, params.shared_w1, params.shared_w3,
                       params.shared_w2)
    stats = stages.MoEStats(
        drops_dispatch=drops_dispatch,
        drops_slot=drops_slot,
        pre_max=ps.plan.pre_max,
        post_max=ps.plan.post_max,
        max_slot_load=valid.sum(axis=1).max().astype(_I32),
        counts=gs.gate_out.counts,
    )
    return y.astype(x.dtype), gs.gate_out.aux_loss, stats
