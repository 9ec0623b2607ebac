"""Replica weight distribution and gradient reduction (paper S6.1 on TPU).

The paper streams expert weights from each main's home rank to its replicas
with persistent tile kernels over one-sided RSN stores; gradients flow back
with the mirrored reduction.  On TPU the wire belongs to XLA, so we express
the same traffic as a collective whose *transpose is exactly the paper's
backward* (DESIGN.md S2):

  forward : replica_w = psum_scatter_{EP}( onehot(slot_wants_my_expert) @ w_local )
  backward: dL/dw_local = onehot^T @ all_gather_{EP}( dL/dreplica_w )

i.e. ``jax.grad`` mechanically derives the replica-gradient reduction onto
main experts -- the training-equivalence property of S4.2 holds by
construction rather than by a hand-written mirror kernel.

Chunking over the packed weight axis plays the role of the paper's tile
streaming: ``n_chunks`` bounds the transient buffer (R*N_slot*3*D*F/n_chunks)
and gives the XLA latency-hiding scheduler independent transfers to overlap
with gating/reroute compute.  The per-transfer byte volume equals the paper's:
each rank *receives* exactly its N_slot inbound replicas.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.quantize import decode_wire, encode_wire

__all__ = ["replica_selector", "select_local_replicas",
           "materialize_replica_stack"]


def replica_selector(x_slots_flat: jax.Array, local_expert_base: jax.Array,
                     experts_per_rank: int) -> jax.Array:
    """One-hot (R*N_slot, E_local) map: global slot j <- my local expert i.

    ``x_slots_flat`` is the flattened plan slot table (R*N_slot,) of logical
    expert ids (-1 = empty); ``local_expert_base`` is this rank's first main
    expert id.  Empty slots select nothing.  Kept as the reference semantics
    for :func:`select_local_replicas` (the hot path uses the gather form: the
    dense ``je,edf->jdf`` einsum is an (R*N_slot, E_local) matmul over the
    full weight tensor, where a masked row gather moves only the selected
    rows).
    """
    local_idx = x_slots_flat - local_expert_base  # (R*N_slot,)
    in_range = (local_idx >= 0) & (local_idx < experts_per_rank)
    onehot = jax.nn.one_hot(
        jnp.where(in_range, local_idx, 0), experts_per_rank, dtype=jnp.float32
    )
    return onehot * in_range[:, None].astype(jnp.float32)


@jax.custom_vjp
def _take_rows(w: jax.Array, idx: jax.Array) -> jax.Array:
    """``w[idx]`` for a short (n,) vector of in-range row indices.

    One dynamic slice per row: the TPU compiler lowers a gather of whole
    expert rows by slicing the entire tensor into tiles first, which
    rewrites all of ``w`` to read ``n`` rows of it.  The transpose is the
    gather's, a scatter-add of the row cotangents onto ``w``.
    """
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(w, idx[j], 1)
                            for j in range(idx.shape[0])])


def _take_rows_fwd(w, idx):
    return _take_rows(w, idx), (w, idx)


def _take_rows_bwd(res, ct):
    w, idx = res
    return jnp.zeros_like(w).at[idx].add(ct), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def select_local_replicas(w_local: jax.Array, x_slots_flat: jax.Array,
                          local_expert_base: jax.Array) -> jax.Array:
    """(R*N_slot, ...) partial replica tensor via a masked row gather.

    Equals ``einsum('je,edf->jdf', replica_selector(...), w_local)`` but
    moves only the selected rows: slots bound to one of this rank's mains
    copy that expert's rows, every other slot contributes zeros (so the
    cross-rank psum still sums to exactly one home contribution per slot).
    The transpose under ``jax.grad`` is a segment-sum of replica gradients
    onto mains -- the same reduction the one-hot matmul transposed into.
    """
    epr = w_local.shape[0]
    local_idx = x_slots_flat - local_expert_base          # (R*N_slot,)
    in_range = (local_idx >= 0) & (local_idx < epr)
    rows = _take_rows(w_local, jnp.clip(local_idx, 0, epr - 1))
    return jnp.where(in_range[:, None, None], rows,
                     jnp.zeros((), w_local.dtype))


def _scatter_replicas(partial: jax.Array, axis_name, racks: int) -> jax.Array:
    """Reduce-scatter one (R, N_slot, ...) partial onto this rank's slots.

    Flat EP axis (``axis_name`` a string): a single ``psum_scatter``.

    Factored ``(rack_axis, lane_axis)`` EP (``axis_name`` a 2-tuple): the
    paper's tiered replica streaming (S6.1) expressed as two collectives --

      stage 1 (scale-up): ``psum_scatter`` over the lane axis aggregates, per
        destination rack, the whole rack's contributions onto the same-lane
        member, so each home's weights leave the rack at most once per
        destination rack;
      stage 2 (scale-out): ``psum_scatter`` over the rack axis lands each
        rack-aggregate on its final rank.  Slots bound intra-rack contribute
        zero blocks here, so the thin fabric only carries cross-rack
        replicas' payloads in substance.

    Every slot has exactly one nonzero (home) contribution, so both shapes
    produce bit-identical replica weights.
    """
    R = partial.shape[0]
    if isinstance(axis_name, (tuple, list)):
        rack_axis, lane_axis = axis_name
        t = partial.reshape((racks, R // racks) + partial.shape[1:])
        t = jax.lax.psum_scatter(t, lane_axis, scatter_dimension=1,
                                 tiled=False)          # (G, n_slot, ...)
        return jax.lax.psum_scatter(t, rack_axis, scatter_dimension=0,
                                    tiled=False)       # (n_slot, ...)
    return jax.lax.psum_scatter(partial, axis_name, scatter_dimension=0,
                                tiled=False)


def materialize_replica_stack(
    ws: tuple[jax.Array, ...],
    x_slots: jax.Array,
    my_rank: jax.Array,
    axis_name: str | tuple[str, str] | None,
    *,
    n_chunks: int = 1,
    racks: int = 1,
    wire_dtype: str = "none",
) -> tuple[jax.Array, ...]:
    """Gather this rank's replica weights from their home ranks.

    Select first, then pack: each tensor of ``ws`` gives up only the
    ``R*N_slot`` rows the slot table asks for (:func:`select_local_replicas`;
    zero for slots homed on another rank), so no tensor with ``E_local``
    rows is copied -- the bytes moved are the replicas' own.  The partials
    are encoded for the wire and packed along their trailing dims into one
    ``(R, N_slot, total)`` payload, so w1/w3/w2 share ONE collective
    schedule; ``psum_scatter`` is elementwise over the packed axis, so each
    returned tensor is bit-identical to its standalone transfer.

    ``wire_dtype`` quantizes the stream (DESIGN.md S12): each selected row is
    encoded at the home rank (per-row symmetric int8, fp32 scales packed
    in-band by :func:`repro.core.quantize.encode_wire`, or a bf16 cast) --
    the codec is per row along the last axis, so encoding the selected rows
    equals selecting the encoded rows.  The reduction stays exact on encoded
    payloads because every slot has exactly ONE nonzero (home) contribution
    and all-zero rows encode to scale 0, so the cross-rank sum reproduces
    the home encoding bit-for-bit; decode happens once on the receiver.
    Replica weights are then a quantized image of their mains (lossy at
    int8/bf16) while mains stay exact.

    Args:
      ws: per-expert weight tensors, each (E_local, ...) with identical
        leading dim (e.g. ``(w1, w3, w2)``): this rank's mains.
      x_slots: (R, N_slot) the plan's slot table (identical on all ranks).
      my_rank: scalar EP rank index of the caller (rack-major when factored).
      axis_name: shard_map axis of the EP group -- a single axis name, a
        ``(rack_axis, lane_axis)`` tuple for two-stage tiered streaming over
        a factored mesh, or None = single-rank mode (R == 1), where replicas
        are just local gathers.
      n_chunks: tile-streaming knob -- chunks of the packed trailing axis.
      racks: rack count of the factored EP group (ignored for flat axes).

    Returns:
      A tuple of replica tensors, the i-th shaped
      ``(N_slot,) + ws[i].shape[1:]``; zero for empty slots.
    """
    epr = ws[0].shape[0]
    R, n_slot = x_slots.shape
    flat = x_slots.reshape(-1)  # (R*n_slot,)
    base = (jnp.asarray(0, flat.dtype) if axis_name is None
            else (my_rank * epr).astype(flat.dtype))
    enc = [encode_wire(select_local_replicas(w, flat, base), wire_dtype)
           for w in ws]                                  # (R*n_slot, ...)
    if axis_name is None:
        # Single-rank EP group (R == 1): the partials are the replicas.
        return tuple(decode_wire(e, wire_dtype, w.dtype)
                     for w, e in zip(ws, enc))

    sizes = [math.prod(e.shape[1:]) for e in enc]
    packed = jnp.concatenate([e.reshape(R, n_slot, -1) for e in enc],
                             axis=-1)                    # (R, n_slot, tot)
    if n_chunks <= 1:
        rep = _scatter_replicas(packed, axis_name, racks)
    else:
        # Tile streaming: chunk the packed axis so the transient send buffer
        # is (R*n_slot, tot/n_chunks) and chunks pipeline under the XLA
        # scheduler.
        tot = packed.shape[-1]
        chunk = -(-tot // n_chunks)
        rep = jnp.concatenate(
            [_scatter_replicas(packed[..., lo:lo + chunk], axis_name, racks)
             for lo in range(0, tot, chunk)], axis=-1)   # (n_slot, tot)
    out = []
    off = 0
    for w, e, sz in zip(ws, enc, sizes):
        r = rep[:, off:off + sz].reshape((n_slot,) + e.shape[1:])
        out.append(decode_wire(r, wire_dtype, w.dtype))
        off += sz
    return tuple(out)
