"""Fused single-sort permutation engine for MoE dispatch (DESIGN.md S2).

The reference dispatch path (:mod:`repro.moe.dispatch`) performs ~5
independent O(N log N) stable argsorts per MoE layer (`token_targets` ->
`occurrence_index` for destinations, `occurrence_index` again inside
`bucket_by_slot`, plus the inverse paths) and builds every buffer with
masked scatter-adds that XLA lowers to serialized scatters.  This engine
collapses all of it into **one** stable sort and pure gathers:

  1. the occurrence index of each routing item within its expert group is a
     histogram cumsum (a vectorised scan over (N, E) one-hots -- no sort);
  2. destination rank *and* destination physical slot are both known on the
     source rank (`slot_of` is derived from the replicated plan), so a single
     stable argsort of the packed key ``dst * (S+1) + slot`` yields items
     grouped by destination rank and, within each rank group, already grouped
     by destination slot;
  3. send buffers are gathers from the saved permutation (`perm`); the item
     -> (dst, pos) inverse is the argsort-of-permutation, materialised with a
     unique-index scatter (`zeros.at[perm].set(iota)`), never a scatter-add;
  4. a tiny per-(dst, slot) count matrix rides the all_to_all as metadata, so
     the *receiver* reconstructs its slot buffers, validity masks and the
     full inverse path purely from cumsums of counts and gathers -- the
     receive side needs **no sort at all** (and no expert-id buffer: the
     count matrix subsumes `send_e` on the wire).

Capacity/drop semantics match the reference path: `cap_pair` bounds tokens
per (src, dst) pair and `cap_slot` bounds tokens per physical slot; overflow
is dropped and counted.  Items routed to a rank that does not host their
expert (a plan bug) sort to the *end* of the rank group (sentinel slot S) and
are counted as slot drops on the receiver, exactly like the reference path
parks them past the last slot.  At zero-drop capacities the fused and
reference paths produce bit-identical layer outputs: every item's buffer row
holds the same activation, the grouped FFN is row-independent, and the
combine reduces the k contributions of each token in the same order.

On a two-level (rack x lane) topology the SAME single sort serves the
hierarchical wire: destination ranks are rack-major, so the packed key
``dst * (S+1) + slot`` is already the ``(rack, lane, slot)`` key, and
:func:`two_hop_all_to_all` replays the flat exchange as an inter-rack hop of
rack-aggregated payloads followed by an intra-rack scatter (DESIGN.md S9).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.planner import token_targets

__all__ = [
    "FusedDispatch",
    "BucketMeta",
    "ReplicatedBucket",
    "occurrence_by_histogram",
    "fused_dispatch",
    "fused_bucket",
    "fused_unbucket",
    "fused_combine",
    "fused_replicated_bucket",
    "fused_replicated_combine",
    "two_hop_all_to_all",
]

_I32 = jnp.int32

# Expert FFN output over the physical slots: one (num_slots, cap_slot, D)
# array, or its consecutive slot ranges (mains, then replicas).
SlotOut = jax.Array | tuple[jax.Array, ...]


class FusedDispatch(NamedTuple):
    """Source-side dispatch state: send buffers + saved permutation inverse."""

    send_x: jax.Array       # (R, cap_pair, D) slot-sorted send buffers
    send_counts: jax.Array  # (R, S+1) kept items per (dst, dst-slot); col S =
                            #   items whose expert the destination doesn't host
    item_dst: jax.Array     # (N,) destination rank per item (-1 dropped)
    item_pos: jax.Array     # (N,) position within the (src, dst) pair buffer
    item_kept: jax.Array    # (N,) bool, False = dropped at pair capacity
    drops: jax.Array        # () int32 items dropped at pair capacity


class BucketMeta(NamedTuple):
    """Receiver-side inverse map: receive position -> slot-buffer position."""

    slot: jax.Array   # (R, cap_pair) slot of each receive position (clipped)
    pos: jax.Array    # (R, cap_pair) row within that slot buffer (clipped)
    valid: jax.Array  # (R, cap_pair) bool


class ReplicatedBucket(NamedTuple):
    """Replicated-mode bucket state: this rank's share of the shared items."""

    xs: jax.Array         # (num_slots, cap_slot, D) slot buffers
    valid: jax.Array      # (num_slots, cap_slot) bool
    item_slot: jax.Array  # (N,) slot of each item on this rank (sentinel = S)
    item_pos: jax.Array   # (N,) row within that slot buffer
    item_ok: jax.Array    # (N,) bool: mine, hosted and within capacity
    drops: jax.Array      # () int32 of *my* items dropped (unhosted/overflow)


def two_hop_all_to_all(
    buf: jax.Array,
    *,
    racks: int,
    rack_axis: str,
    lane_axis: str,
    reverse: bool = False,
) -> jax.Array:
    """Tiered EP exchange of a destination-major buffer (DESIGN.md S9).

    ``buf`` is ``(R, ...)`` with one leading row per destination EP rank in
    rack-major order -- exactly the layout :func:`fused_dispatch` emits,
    because its packed sort key ``dst * (S+1) + slot`` *is* the hierarchical
    ``(rack, lane, slot)`` key when ``dst = rack * L + lane``.  The wire is
    two hops over the factored ``(rack_axis, lane_axis)`` mesh:

      hop 1 (scale-out): ``all_to_all`` over ``rack_axis`` moves, per remote
        rack, ONE rack-aggregated payload of ``L`` destination-lane rows to
        the *same-lane* peer in that rack (rail-aligned, so the thin fabric
        sees ``G`` messages of ``L*cap`` rows instead of ``R`` of ``cap``);
      hop 2 (scale-up): ``all_to_all`` over ``lane_axis`` scatters each row
        to its final lane inside the rack.

    Both hops are involutions and commute per-element, so the composite is a
    pure relabelling: the result rows are ``recv[src] = send_{src}[me]`` --
    bit-identical to a flat ``all_to_all`` over the combined axis.  The
    count-matrix metadata rides the same path (any trailing shape works).
    ``reverse=True`` applies the inverse permutation (lane hop first) for the
    return wire.
    """
    R = buf.shape[0]
    if R % racks != 0:
        raise ValueError(f"R={R} must factor into racks={racks}")
    t = buf.reshape((racks, R // racks) + buf.shape[1:])
    hops = [(rack_axis, 0), (lane_axis, 1)]
    for axis, dim in hops[::-1] if reverse else hops:
        t = jax.lax.all_to_all(t, axis, dim, dim, tiled=True)
    return t.reshape((R,) + buf.shape[1:])


def occurrence_by_histogram(ids: jax.Array, num_groups: int) -> jax.Array:
    """j-th occurrence of each item within its id group, without sorting.

    A cumulative histogram over (N, G) one-hots: ``occ[i] = #{i' < i :
    ids[i'] == ids[i]}``.  O(N*G) work but a fully vectorised scan -- for the
    group counts this engine sees (<= a few hundred experts / slots) it beats
    a stable N log N sort on both TPU and CPU, freeing the single sort budget
    for the packed destination key.
    """
    oh = ids[:, None] == jnp.arange(num_groups, dtype=ids.dtype)[None, :]
    cum = jnp.cumsum(oh.astype(_I32), axis=0)
    return jnp.take_along_axis(
        cum, jnp.clip(ids, 0, num_groups - 1)[:, None].astype(_I32), axis=1
    )[:, 0] - 1


def _group_bounds(sorted_keys: jax.Array, num_keys: int):
    """(start, count) of each key group within a sorted key array."""
    probe = jnp.arange(num_keys, dtype=sorted_keys.dtype)
    start = jnp.searchsorted(sorted_keys, probe, side="left").astype(_I32)
    end = jnp.searchsorted(sorted_keys, probe, side="right").astype(_I32)
    return start, end - start


def fused_dispatch(
    x_local: jax.Array,
    expert_ids: jax.Array,
    cum_q_row: jax.Array,
    dst_slot_of: jax.Array,
    *,
    num_slots: int,
    cap_pair: int,
    occ_offset: jax.Array | None = None,
    routed: jax.Array | None = None,
) -> FusedDispatch:
    """Single-sort dispatch: pack the key, sort once, gather everything.

    Args:
      x_local: (T, D) local tokens.
      expert_ids: (T, k) selected logical experts.
      cum_q_row: (E, R) inclusive cumulative reroute quota of this source
        rank (``plan.cum_q[my]``, precomputed at solve time).
      dst_slot_of: (R, E) physical slot of expert e on rank r, -1 if not
        hosted (``physical_slot_of(layout, plan.x)``, replicated plan state).
      num_slots: physical slots per rank (E/R mains + n_slot redundants).
      cap_pair: static capacity per (src, dst) pair buffer.
      occ_offset: optional (E,) per-expert occurrence offset.  The overlap
        driver (``repro.moe.stages``) dispatches the microbatch in token
        chunks sharing one plan; continuing the occurrence index across
        chunks makes every item hit the exact same instance as the unchunked
        dispatch, so the shared quota table stays exactly honoured.
      routed: optional (T, k) bool, False for a pair an expert share does not
        route here (its id is -1): it is never sent, kept or counted as a
        drop.
    """
    T, k = expert_ids.shape
    E, R = cum_q_row.shape
    S1 = num_slots + 1  # +1 sentinel column for not-hosted items

    e = expert_ids.reshape(-1).astype(_I32)                      # (N,)
    n = e.shape[0]
    occ = occurrence_by_histogram(e, E)                          # no sort
    if occ_offset is not None:
        occ = occ + occ_offset[e]
    # Destination rank: first rank whose cumulative quota exceeds occ (S5.2),
    # shared with the reference path so the semantics cannot diverge.
    dst = token_targets(e, cumq=cum_q_row, occ=occ)
    slot = dst_slot_of[dst, e]                                   # (N,)
    slot = jnp.where(slot >= 0, slot, num_slots).astype(_I32)    # sentinel
    if routed is not None:
        # Past every rank group: the sort puts unrouted pairs last.
        routed = routed.reshape(-1)
        dst = jnp.where(routed, dst, R)

    # --- THE sort: packed (dst, slot) key, one stable pass -----------------
    key = dst * S1 + slot
    perm = jnp.argsort(key, stable=True).astype(_I32)            # (N,)
    sorted_key = key[perm]
    sorted_dst = sorted_key // S1

    # Rank-group geometry from the sorted keys (log-time probes, no scan).
    dst_start, dst_cnt = _group_bounds(sorted_dst, R)            # (R,), (R,)
    pos_sorted = jnp.arange(n, dtype=_I32) - dst_start[sorted_dst]
    # Inverse path = argsort of the permutation: a unique-index scatter.
    item_pos = jnp.zeros((n,), _I32).at[perm].set(pos_sorted)
    kept = item_pos < cap_pair
    if routed is None:
        drops = jnp.sum(~kept).astype(_I32)
    else:
        kept = kept & routed
        drops = jnp.sum(routed & ~kept).astype(_I32)

    # --- send buffers: pure gathers from the saved permutation -------------
    col = jnp.arange(cap_pair, dtype=_I32)
    gather_idx = dst_start[:, None] + col[None, :]               # (R, cap)
    in_row = col[None, :] < dst_cnt[:, None]
    src_item = perm[jnp.clip(gather_idx, 0, n - 1)]              # (R, cap)
    tok = src_item // k
    send_x = jnp.where(
        in_row[:, :, None], x_local[tok], jnp.zeros((), x_local.dtype)
    )

    # --- per-(dst, slot) kept counts: the a2a metadata ---------------------
    pair_start, pair_cnt = _group_bounds(sorted_key, R * S1)
    pair_start = pair_start.reshape(R, S1)
    pair_end = pair_start + pair_cnt.reshape(R, S1)
    kept_lim = (dst_start + jnp.minimum(dst_cnt, cap_pair))[:, None]
    send_counts = (
        jnp.minimum(pair_end, kept_lim) - jnp.minimum(pair_start, kept_lim)
    ).astype(_I32)

    return FusedDispatch(
        send_x=send_x,
        send_counts=send_counts,
        item_dst=jnp.where(kept, dst, -1),
        item_pos=item_pos,
        item_kept=kept,
        drops=drops,
    )


def fused_bucket(
    recv_x: jax.Array,
    recv_counts: jax.Array,
    *,
    num_slots: int,
    cap_slot: int,
):
    """Sort-free receive-side bucketing from the count metadata.

    Senders transmit slot-sorted rows plus per-(src, slot) counts, so the
    bucket layout is fully determined by cumsums of a tiny (R, S+1) matrix:
    items of slot g are the concatenation, in source order, of each source
    row's g-segment.  Slot buffers, validity and the inverse map are all
    gathers -- no occurrence sort, no scatter.

    Args:
      recv_x: (R, cap_pair, D) received token buffers (slot-sorted rows).
      recv_counts: (R, S+1) per-source kept counts by destination slot;
        column S counts items whose expert this rank does not host.

    Returns:
      (xs, valid, meta, drops): slot buffers (num_slots, cap_slot, D), their
      validity mask, the :class:`BucketMeta` inverse map, and the count of
      dropped items (not hosted + slot-capacity overflow).
    """
    R, cap_pair, D = recv_x.shape
    counts = recv_counts[:, :num_slots].astype(_I32)             # (R, G)

    # Row geometry: where each slot segment starts within its source row.
    row_cum = jnp.cumsum(recv_counts.astype(_I32), axis=1)       # (R, S+1)
    row_start = row_cum - recv_counts.astype(_I32)               # exclusive
    # Column geometry: where each source's segment lands within the bucket.
    col_cum = jnp.cumsum(counts, axis=0)                         # (R, G) incl
    col_base = col_cum - counts                                  # exclusive
    tot = col_cum[-1]                                            # (G,)

    # --- slot buffers as gathers -------------------------------------------
    p = jnp.arange(cap_slot, dtype=_I32)
    # Source of bucket entry (g, p): first src whose cumulative count > p.
    src = jnp.sum(
        col_cum.T[:, None, :] <= p[None, :, None], axis=-1
    ).astype(_I32)                                               # (G, cap_slot)
    src = jnp.minimum(src, R - 1)
    g_idx = jnp.arange(num_slots, dtype=_I32)[:, None]
    row_pos = row_start[src, g_idx] + (p[None, :] - col_base[src, g_idx])
    valid = p[None, :] < jnp.minimum(tot, cap_slot)[:, None]
    flat = recv_x.reshape(-1, D)
    flat_idx = jnp.clip(src * cap_pair + row_pos, 0, R * cap_pair - 1)
    xs = jnp.where(
        valid[:, :, None], flat[flat_idx], jnp.zeros((), recv_x.dtype)
    )

    # --- inverse map: receive position -> bucket position ------------------
    c = jnp.arange(cap_pair, dtype=_I32)
    # Slot of receive position (r, c): first slot whose row cumsum > c.
    g_rc = jnp.sum(row_cum[:, None, :] <= c[None, :, None], axis=-1)
    g_safe = jnp.minimum(g_rc, num_slots - 1).astype(_I32)
    r_idx = jnp.arange(R, dtype=_I32)[:, None]
    p_rc = col_base[r_idx, g_safe] + (c[None, :] - row_start[r_idx, g_safe])
    ok = (g_rc < num_slots) & (p_rc < cap_slot)
    meta = BucketMeta(
        slot=g_safe, pos=jnp.clip(p_rc, 0, cap_slot - 1), valid=ok
    )

    drops = (
        recv_counts[:, num_slots].sum()
        + jnp.maximum(tot - cap_slot, 0).sum()
    ).astype(_I32)
    return xs, valid, meta, drops


def _slot_rows(out: SlotOut, slot: jax.Array, pos: jax.Array) -> jax.Array:
    """``out[slot, pos]`` of the (num_slots, cap_slot, D) slot outputs.

    ``out`` is the array itself or a tuple of its consecutive slot ranges
    (the mains' outputs, then the replicas'): each range answers the slots
    it holds, so the concatenation is never built.  Indices are clamped as
    a gather on the whole array clamps them.
    """
    if not isinstance(out, tuple):
        return out[slot, pos]
    rows, base = None, 0
    for part in out:
        local = slot - base
        r = part[jnp.clip(local, 0, part.shape[0] - 1), pos]
        rows = r if rows is None else jnp.where((local >= 0)[..., None],
                                                r, rows)
        base += part.shape[0]
    return rows


def fused_unbucket(out: SlotOut, meta: BucketMeta) -> jax.Array:
    """Inverse of :func:`fused_bucket`: a pure gather back to (R, cap_pair)."""
    ret = _slot_rows(out, meta.slot, meta.pos)            # (R, cap_pair, D)
    return jnp.where(meta.valid[:, :, None], ret, jnp.zeros((), ret.dtype))


def _kept_rows(rows: jax.Array, kept: jax.Array,
               weights: jax.Array) -> jax.Array:
    """(N, D) gathered rows times their items' gate weights, zero where an
    item was not kept.

    A select, not a multiply by a zero weight: a dropped item's gather
    reads a placeholder row, which the count-aware FFN may have left
    unwritten, and ``NaN * 0`` is NaN."""
    w = weights.reshape(-1)[:, None].astype(rows.dtype)
    return jnp.where(kept[:, None], rows * w, jnp.zeros((), rows.dtype))


def _tokenwise_sum(vals: jax.Array) -> jax.Array:
    """(T, k, D) -> (T, D) as a strict left fold over k.

    A tree-shaped ``sum(axis=1)`` would reassociate the float additions; the
    reference combine's scatter-add applies the k contributions of a token in
    item order, so the fold order is what makes fused == reference bitwise.
    """
    y = vals[:, 0]
    for i in range(1, vals.shape[1]):
        y = y + vals[:, i]
    return y


def fused_combine(
    ret_x: jax.Array,
    disp: FusedDispatch,
    weights: jax.Array,
) -> jax.Array:
    """Weighted combine, scatter-free.

    Items are token-major (k consecutive items per token), so the per-token
    reduction is a reshape + axis sum instead of the reference path's
    ``y.at[items_t].add`` scatter; the k contributions reduce in the same
    order, preserving bit-identity with the reference combine.
    """
    T, k = weights.shape
    D = ret_x.shape[-1]
    kept = disp.item_kept
    safe_dst = jnp.where(kept, disp.item_dst, 0)
    safe_pos = jnp.where(kept, disp.item_pos, 0)
    vals = _kept_rows(ret_x[safe_dst, safe_pos], kept, weights)
    return _tokenwise_sum(vals.reshape(T, k, D))


def fused_replicated_bucket(
    x: jax.Array,
    expert_ids: jax.Array,
    cum_u: jax.Array,
    my_rank: jax.Array,
    slot_of: jax.Array,
    *,
    num_slots: int,
    cap_slot: int,
    occ_offset: jax.Array | None = None,
    routed: jax.Array | None = None,
) -> ReplicatedBucket:
    """Replicated-mode bucketing: one sort over this rank's owned share.

    Tokens are identical on every EP rank; item j of expert e belongs to the
    instance whose cumulative quota covers j.  Items this rank does not own
    (or whose expert it does not host) take the sentinel slot S and sort to
    the end; everything else is the same single-sort + gather scheme.

    Args:
      x: (T, D) the (replicated) tokens.
      expert_ids: (T, k) selected logical experts.
      cum_u: (E, R) inclusive cumulative instance quota (``plan.cum_u``).
      my_rank: scalar EP rank of the caller.
      slot_of: (E,) this rank's physical slot per expert (-1 = not hosted).
      occ_offset: optional (E,) per-expert occurrence offset continuing the
        global occurrence index across overlap chunks (see
        :func:`fused_dispatch`), so chunked ownership equals unchunked.
      routed: optional (T, k) bool as in :func:`fused_dispatch`: a pair an
        expert share does not route here is no rank's, so never a drop.
    """
    T, k = expert_ids.shape
    E = cum_u.shape[0]
    e = expert_ids.reshape(-1).astype(_I32)
    n = e.shape[0]
    occ = occurrence_by_histogram(e, E)
    if occ_offset is not None:
        occ = occ + occ_offset[e]
    owner = token_targets(e, cumq=cum_u, occ=occ)
    mine = owner == my_rank
    if routed is not None:
        mine = mine & routed.reshape(-1)
    slot = slot_of[e]
    hosted = slot >= 0
    key = jnp.where(mine & hosted, slot, num_slots).astype(_I32)

    perm = jnp.argsort(key, stable=True).astype(_I32)
    sorted_key = key[perm]
    start, cnt = _group_bounds(sorted_key, num_slots + 1)
    pos_sorted = jnp.arange(n, dtype=_I32) - start[sorted_key]
    item_pos = jnp.zeros((n,), _I32).at[perm].set(pos_sorted)
    item_ok = (key < num_slots) & (item_pos < cap_slot)
    drops = jnp.sum(mine & ~item_ok).astype(_I32)

    p = jnp.arange(cap_slot, dtype=_I32)
    gather_idx = start[:num_slots, None] + p[None, :]
    valid = p[None, :] < jnp.minimum(cnt[:num_slots], cap_slot)[:, None]
    src_item = perm[jnp.clip(gather_idx, 0, n - 1)]
    xs = jnp.where(
        valid[:, :, None], x[src_item // k], jnp.zeros((), x.dtype)
    )
    return ReplicatedBucket(
        xs=xs, valid=valid, item_slot=key, item_pos=item_pos,
        item_ok=item_ok, drops=drops,
    )


def fused_replicated_combine(
    out: SlotOut,
    bucket: ReplicatedBucket,
    weights: jax.Array,
) -> jax.Array:
    """Per-item gather from the slot buffers + token-major weighted sum."""
    T, k = weights.shape
    ok = bucket.item_ok
    safe_slot = jnp.where(ok, bucket.item_slot, 0)
    safe_pos = jnp.where(ok, bucket.item_pos, 0)
    rows = _slot_rows(out, safe_slot, safe_pos)
    vals = _kept_rows(rows, ok, weights)
    return _tokenwise_sum(vals.reshape(T, k, rows.shape[-1]))
