"""Staged MoE execution engine: typed stage boundaries + chunked overlap.

``moe_layer_local`` used to be a ~220-line monolith interleaving gating,
load gathering, plan solving, replica streaming, dispatch, FFN and combine,
branched three ways over ``dispatch_mode`` -- with no seam at which chunk
*i+1*'s dispatch all_to_all could run under chunk *i*'s grouped FFN.  This
module decomposes it into six explicit stages (DESIGN.md S11):

  GateStage        gate + exact load gather            -> GateState
  PlanStage        balancer solve + slot table         -> PlanState
  DistributeStage  replica slots' weight streaming     -> DistributeState
  DispatchStage    reroute + pack + (two-hop) a2a      -> DispatchState
  ComputeStage     grouped FFN over physical slots     -> slot outputs
  CombineStage     inverse wire + weighted reduce      -> (T_chunk, D)

and rebuilds the layer as :func:`run_staged_moe`, a thin driver that
composes them per ``dispatch_mode``.  The stage contract: each stage reads
only the typed state of earlier stages; gate/plan/distribute run ONCE per
microbatch (the plan is solved on the *full-batch* load, so balancing and
zero-drop bit-identity are untouched by chunking); dispatch/compute/combine
run once per overlap chunk.

``MoEConfig.overlap_chunks = N`` splits the microbatch into N token chunks
sharing that one plan and software-pipelines them: chunk *i+1*'s dispatch
(including its all_to_all) is issued before chunk *i*'s FFN + combine
consume their buffers, so the XLA latency-hiding scheduler can run the wire
under compute -- double-buffered through the packed (dst, slot) machinery
of :mod:`repro.moe.permute`.  Per-expert occurrence offsets
(:func:`chunk_occ_offsets`) continue the global occurrence index across
chunks, so every item routes to the exact same expert instance as the
unchunked dispatch and chunked output is bit-identical at zero-drop
capacities (tests/test_stages.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.plan_check import PlanViolationError
from repro.core import balancer as balancer_mod
from repro.core.layout import physical_slot_of
from repro.fault.injector import PlannerFault, SolveTimeout, TransferFault
from repro.core.quantize import (
    decode_wire,
    encode_wire,
    payload_bytes_per_item,
    split_wire_int8,
)
from repro.kernels.grouped_gemm.ref import stack_layer
from repro.moe.distribute import materialize_replica_stack
from repro.moe.expert import grouped_ffn
from repro.moe.gating import GateOut, gate, rack_copy_volumes
from repro.moe.permute import (
    fused_bucket,
    fused_combine,
    fused_dispatch,
    fused_replicated_bucket,
    fused_replicated_combine,
    fused_unbucket,
    two_hop_all_to_all,
)
from repro.moe.reference import swiglu

__all__ = [
    "MoEStats",
    "StageCtx",
    "GateState",
    "PlanState",
    "DistributeState",
    "DispatchState",
    "ResilienceConfig",
    "Resilience",
    "make_stage_ctx",
    "gate_stage",
    "plan_stage",
    "distribute_stage",
    "dispatch_stage",
    "compute_stage",
    "combine_stage",
    "screen_payload",
    "chunk_bounds",
    "chunk_occ_offsets",
    "run_staged_moe",
]

_I32 = jnp.int32


class MoEStats(NamedTuple):
    drops_dispatch: jax.Array   # () items dropped at pair-capacity
    drops_slot: jax.Array       # () items dropped at slot-capacity
    pre_max: jax.Array          # () pre-balance max rank load
    post_max: jax.Array         # () post-balance max rank load
    max_slot_load: jax.Array    # () busiest physical slot occupancy
                                #    (max over overlap chunks when chunked)
    counts: jax.Array           # (E,) local per-expert load
    tier_tokens: jax.Array | None = None    # (3,) [local, intra, inter]
    tier_replicas: jax.Array | None = None  # (2,) [intra, inter] (rack-aware)
    tier_bytes: jax.Array | None = None     # (3,) one-way dispatch-wire bytes
                                #    per tier = tier_tokens * the per-item
                                #    payload width of cfg.wire_dtype
                                #    (repro.core.quantize, DESIGN.md S12)
    # At-gate twin of tier_tokens (rack-aware non-replicated modes;
    # DESIGN.md S14): deduplicated payload copies measured at the
    # gate against the home placement, BEFORE the plan's reroute --
    # gate_tier_tokens[2] is the aggregated hop-1 volume an M-rack-limited
    # gate bounds to <= M copies per token, vs tier_tokens[2] which is what
    # the solved plan actually ships (in items).
    gate_tier_tokens: jax.Array | None = None  # (3,) [local, intra, inter]
    # Resilience counters (populated when run with a Resilience; DESIGN.md
    # S13).  fallback_plans counts degradation-ladder activations of THIS
    # call (solve -> last-good -> no-balance, plus transfer-exhaustion
    # downgrades); dropped_payload_tokens counts NaN/Inf payload rows
    # screened out at stage boundaries; quarantined_ranks mirrors the
    # health state the plan was solved under.
    fallback_plans: jax.Array | None = None          # () int32
    dropped_payload_tokens: jax.Array | None = None  # () int32
    quarantined_ranks: jax.Array | None = None       # () int32
    # Rows whose products the expert FFN computed, over every slot and
    # overlap chunk, the count-aware kernel's tile padding included.
    rows_run: jax.Array | None = None                # () int32


class StageCtx(NamedTuple):
    """Validated static context shared by every stage (no array state)."""

    cfg: Any                                # MoEConfig (duck-typed: no import
                                            # of repro.moe.layer -> no cycle)
    axis_name: str | tuple[str, str] | None
    factored: bool
    rack_axis: str | None
    lane_axis: str | None


class GateState(NamedTuple):
    """GateStage output: routing decisions + the exact EP load matrix."""

    gate_out: GateOut    # expert_ids/weights/counts/aux_loss for the full T
    lam: jax.Array       # (R, E) exact per-rank per-expert load
    my: jax.Array        # () this rank's EP index (rack-major when factored)
    gate_tier_tokens: jax.Array | None = None  # (3,) EP-global at-gate
                         #    deduplicated payload copies by tier (rack-aware
                         #    non-replicated modes; repro.moe.gating
                         #    .rack_copy_volumes summed over source ranks)


class PlanState(NamedTuple):
    """PlanStage output: the solved plan + replicated slot table."""

    plan: Any            # repro.core.balancer Plan (replicated on all ranks)
    slot_of_all: jax.Array   # (R, E) physical slot of e on r, -1 not hosted


class DistributeState(NamedTuple):
    """DistributeStage output: the weights of every physical slot.

    Slot ``j < E_local`` reads main ``j`` -- the parameter arrays themselves,
    never copied -- and slot ``E_local + i`` reads replica ``i``.
    """

    w1: jax.Array        # (E_local, D, F) mains
    w3: jax.Array        # (E_local, D, F)
    w2: jax.Array        # (E_local, F, D)
    w1r: jax.Array       # (N_slot, D, F) streamed replicas
    w3r: jax.Array       # (N_slot, D, F)
    w2r: jax.Array       # (N_slot, F, D)
    layer: jax.Array | None = None  # the mains' layer index when they are
                         #    a segment's whole stacks (MoEParams.layer)


class DispatchState(NamedTuple):
    """DispatchStage output for ONE overlap chunk.

    ``xs``/``valid`` are the slot buffers ComputeStage consumes; ``inverse``
    is the mode-specific state CombineStage needs to route FFN outputs back
    (a2a and hier_a2a: (FusedDispatch, BucketMeta); replicated:
    ReplicatedBucket).  Stages communicate ONLY through these fields -- the
    stage-boundary lint rule (DESIGN.md S11) keeps the underlying engine
    primitives from being called outside this module.
    """

    xs: jax.Array        # (num_slots, cap_slot, D) slot buffers; int8 codes
                         #    on the end-to-end quantized path (see xs_scale)
    valid: jax.Array     # (num_slots, cap_slot) bool
    inverse: Any         # mode-specific inverse-path state (see above)
    drops_dispatch: jax.Array   # () pair-capacity drops this chunk
    drops_slot: jax.Array       # () slot-capacity drops this chunk
    xs_scale: jax.Array | None = None   # (num_slots, cap_slot) fp32 per-row
                         #    wire scales when wire_dtype == ffn_dtype ==
                         #    "int8": the slot buffers stay encoded and feed
                         #    the w8a8 kernel directly (no dequant round-trip)
    max_rows: int | None = None  # static: the items bucketed, which bound
                         #    the valid rows of all slots together


# --------------------------------------------------------------------------
# Resilience: graceful-degradation ladder + payload screening (DESIGN.md S13)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Knobs of the degradation ladder.

    ``solve_deadline_s`` bounds the *host-side* wall time of one eager plan
    solve; exceeding it is treated as a solve failure (under jit the solve
    is traced, not timed -- the deadline is an eager/serving-path guard).
    ``max_transfer_retries`` bounds retry of *transient* transfer faults,
    each backed off by ``retry_backoff_s * 2**attempt`` seconds.
    ``screen_payloads`` switches the NaN/Inf stage-boundary screen.
    """

    solve_deadline_s: float | None = None
    max_transfer_retries: int = 2
    retry_backoff_s: float = 0.0
    screen_payloads: bool = True


class Resilience:
    """Host-side resilience state threaded through one MoE layer's stages.

    Holds the fault injector (optional), the rank-health state feeding the
    planner (optional), the last-good plan cache, and the fault counters.
    The degradation ladder it implements in :meth:`solve_with_ladder`:

        solve (health-weighted)  -- normal path; concrete plans are cached
          |  PlannerFault / SolveTimeout / PlanViolationError
          v
        last-good cached plan    -- stale but valid; quotas may clamp
          |  no cached plan of matching shape
          v
        no_balance_plan          -- home routing, never fails, never stalls

    All ladder logic runs at host/trace time: a compiled JAX step cannot
    raise mid-flight, so faults are decided where the step is *built*.  The
    plan cache stores only concrete (eager) plans -- a traced plan is a
    graph value of one trace and cannot be replayed into another step.
    """

    def __init__(self, cfg: ResilienceConfig = ResilienceConfig(), *,
                 injector=None, health=None, layer: int | None = None):
        self.cfg = cfg
        self.injector = injector
        self.health = health
        self.layer = layer
        self.last_good = None
        self.last_error: Exception | None = None
        self.counters = {
            "fallback_plans": 0,       # ladder activations (any rung)
            "last_good_reuses": 0,     # rung 2 hits
            "no_balance_fallbacks": 0,  # rung 3 hits
            "transfer_retries": 0,     # transient transfer faults retried
            "transfer_fallbacks": 0,   # retry budget exhausted
        }

    # -- planner rung ------------------------------------------------------

    def health_weight(self) -> jax.Array | None:
        if self.health is None:
            return None
        return jnp.asarray(self.health.planner_weights(), jnp.float32)

    def num_quarantined(self) -> int:
        return 0 if self.health is None else self.health.num_quarantined

    # -- distribute rung: live-health relay scheduling ---------------------

    def rank_speed(self):
        """(R,) live relative channel speeds for the relay builder, or None.

        The same :meth:`RankHealth.planner_weights` vector that scales the
        plan's quotas: a half-speed rank's relay channels cost 2x seconds,
        a quarantined rank (weight 0, clamped by the builder) is effectively
        last in every tree -- so replica broadcast trees route *around*
        degraded ranks with the same live signal the planner drains them by.
        """
        if self.health is None:
            return None
        return self.health.planner_weights()

    def relay_schedule(self, plan, expert_bytes: int, home, *,
                       relay_threshold: int = 3, topology=None):
        """Build the plan's replica broadcast schedule under LIVE speeds.

        Host-side companion of :func:`distribute_stage` for a runner that
        models or drives the replica stream explicitly: routing
        ``build_relay_schedule`` through the layer's :class:`Resilience`
        makes the tree itself health-aware.  ``plan`` is a solved
        (concrete) Plan; ``home`` the (E,) home map.
        """
        import numpy as np

        from repro.core import comm_plan

        hosted = np.asarray(plan.hosted).T   # (E, R) expert-major
        return comm_plan.build_relay_schedule(
            hosted, np.asarray(home), expert_bytes,
            relay_threshold=relay_threshold, topology=topology,
            rank_speed=self.rank_speed())

    def solve_with_ladder(self, solve_fn, lam: jax.Array, home: jax.Array,
                          n_slot: int, rack_size: int | None,
                          gate_tier_tokens: jax.Array | None = None):
        """Run ``solve_fn`` through the ladder; always returns a plan."""
        try:
            plan = solve_fn()
        except (PlannerFault, PlanViolationError) as e:
            self.last_error = e
            self.counters["fallback_plans"] += 1
            cached = self.last_good
            if cached is not None and cached.u.shape == (lam.shape[1],
                                                         lam.shape[0]):
                self.counters["last_good_reuses"] += 1
                return cached
            self.counters["no_balance_fallbacks"] += 1
            return balancer_mod.no_balance_plan(lam, home, n_slot, rack_size,
                                                gate_tier_tokens)
        if not isinstance(plan.u, jax.core.Tracer):
            self.last_good = plan
        return plan

    # -- transfer rung -----------------------------------------------------

    def guard_transfer(self) -> None:
        """Bounded retry+backoff over transient transfer faults.

        Returns normally when the transfer may proceed; re-raises the
        :class:`TransferFault` when it is permanent or the retry budget is
        exhausted (the caller then downgrades to a replica-free plan).
        """
        if self.injector is None:
            return
        attempts = self.cfg.max_transfer_retries + 1
        for attempt in range(attempts):
            try:
                self.injector.check_transfer(self.layer)
                return
            except TransferFault as e:
                self.last_error = e
                if not e.transient or attempt == attempts - 1:
                    self.counters["transfer_fallbacks"] += 1
                    raise
                self.counters["transfer_retries"] += 1
                if self.cfg.retry_backoff_s > 0:
                    time.sleep(self.cfg.retry_backoff_s * (2 ** attempt))

    def __repr__(self) -> str:
        live = {k: v for k, v in self.counters.items() if v}
        return f"Resilience(layer={self.layer}, counters={live})"


def screen_payload(xs: jax.Array, valid: jax.Array
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Drop non-finite payload rows at a stage boundary.

    Returns ``(xs, valid, n_dropped)`` where corrupted rows are zeroed AND
    invalidated.  Zeroing matters independently of the mask: the grouped
    FFN multiplies invalid rows by 0, and ``NaN * 0 == NaN`` would leak the
    corruption straight through the mask.  Integer buffers (int8 wire
    codes) pass through -- they cannot encode NaN.
    """
    if not jnp.issubdtype(xs.dtype, jnp.inexact):
        return xs, valid, jnp.zeros((), _I32)
    finite = jnp.isfinite(xs).all(axis=-1)
    dropped = (valid & ~finite).sum().astype(_I32)
    xs = jnp.where(finite[..., None], xs, 0)
    return xs, valid & finite, dropped


def _screen_rows(y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Zero non-finite output rows; returns ``(y, n_dropped)``.

    The combine-side twin of :func:`screen_payload`: a token whose combined
    MoE output went non-finite (corrupted replica weights, FFN overflow)
    contributes zero to the residual stream instead of poisoning it.
    """
    if not jnp.issubdtype(y.dtype, jnp.inexact):
        return y, jnp.zeros((), _I32)
    finite = jnp.isfinite(y).all(axis=-1)
    dropped = (~finite).sum().astype(_I32)
    return jnp.where(finite[:, None], y, 0), dropped


def make_stage_ctx(cfg, axis_name) -> StageCtx:
    """Validate the (dispatch_mode, mesh axis) pairing once, up front."""
    factored = isinstance(axis_name, (tuple, list))
    rack_axis = lane_axis = None
    if factored:
        if len(axis_name) != 2:
            raise ValueError(
                f"factored axis_name must be (rack_axis, lane_axis), "
                f"got {axis_name!r}")
        if cfg.dispatch_mode == "a2a":
            raise ValueError(
                "dispatch_mode='a2a' runs on a flat EP axis; use "
                "'hier_a2a' on a factored (rack, lane) mesh")
        rack_axis, lane_axis = axis_name
    elif cfg.dispatch_mode == "hier_a2a" and axis_name is not None:
        raise ValueError(
            "dispatch_mode='hier_a2a' needs a (rack_axis, lane_axis) "
            "axis_name tuple (or None when ep_size == 1)")
    return StageCtx(cfg=cfg, axis_name=axis_name, factored=factored,
                    rack_axis=rack_axis, lane_axis=lane_axis)


def _my_rank(ctx: StageCtx) -> jax.Array:
    if ctx.factored:
        return (jax.lax.axis_index(ctx.rack_axis) * ctx.cfg.ranks_per_rack
                + jax.lax.axis_index(ctx.lane_axis)).astype(_I32)
    if ctx.axis_name is not None:
        return jax.lax.axis_index(ctx.axis_name).astype(_I32)
    return jnp.asarray(0, _I32)


def _exchange(ctx: StageCtx, buf: jax.Array, *,
              reverse: bool = False) -> jax.Array:
    """(R, ...) destination-major buffer through the EP fabric."""
    if ctx.factored:
        return two_hop_all_to_all(buf, racks=ctx.cfg.racks,
                                  rack_axis=ctx.rack_axis,
                                  lane_axis=ctx.lane_axis, reverse=reverse)
    if ctx.axis_name is not None:
        return jax.lax.all_to_all(buf, ctx.axis_name, 0, 0, tiled=False)
    return buf


# --------------------------------------------------------------------------
# Per-microbatch stages (run once, shared by every overlap chunk)
# --------------------------------------------------------------------------


def gate_stage(ctx: StageCtx, x: jax.Array, router: jax.Array,
               router_bias: jax.Array | None = None) -> GateState:
    """Gate the full microbatch and gather the exact EP load matrix."""
    cfg = ctx.cfg
    R = cfg.ep_size
    gate_out: GateOut = gate(x, router, cfg.gating, bias=router_bias)
    counts = gate_out.counts
    if cfg.gating.holds_share:
        # The plan balances the held block's load only.
        lo = cfg.gating.first_expert
        counts = counts[lo:lo + cfg.gating.held]
    if cfg.dispatch_mode == "replicated":
        # Tokens are identical on every EP rank, so counts are already the
        # EP-group totals -- no collective needed.  Attribute the load to the
        # experts' home ranks (source locality is vacuous here).
        home = cfg.layout.home()
        lam = (jax.nn.one_hot(home, R, dtype=_I32)
               * counts[:, None]).T                                 # (R, E)
        my = _my_rank(ctx)
    elif ctx.axis_name is not None:
        if ctx.factored:
            # Two-step gather mirrors the wire: lanes first, then racks,
            # yielding rack-major (= global rank order) load rows.
            lam = jax.lax.all_gather(counts, ctx.lane_axis)
            lam = jax.lax.all_gather(lam, ctx.rack_axis).reshape(R, -1)
        else:
            lam = jax.lax.all_gather(counts, ctx.axis_name)
        my = _my_rank(ctx)
    else:
        if R != 1:
            raise ValueError("axis_name=None requires ep_size == 1")
        lam = counts[None]
        my = jnp.asarray(0, _I32)
    gate_tiers = None
    if cfg.rack_size is not None and cfg.dispatch_mode != "replicated":
        # At-gate tier accounting (DESIGN.md S14): this rank's deduplicated
        # (token -> destination) payload copies against the home placement,
        # psum-reduced to the EP-global total alongside the load gather.
        gate_tiers = rack_copy_volumes(
            gate_out.expert_ids, cfg.layout.home(),
            num_ranks=R, rack_size=cfg.rack_size, src_rank=my)
        if ctx.factored:
            gate_tiers = jax.lax.psum(
                jax.lax.psum(gate_tiers, ctx.lane_axis), ctx.rack_axis)
        elif ctx.axis_name is not None:
            gate_tiers = jax.lax.psum(gate_tiers, ctx.axis_name)
    return GateState(gate_out=gate_out, lam=lam, my=my,
                     gate_tier_tokens=gate_tiers)


def plan_stage(ctx: StageCtx, gs: GateState, *,
               lam_e_est: jax.Array | None = None,
               resilience: Resilience | None = None) -> PlanState:
    """Solve the balancer on the FULL-batch load (once per microbatch).

    With ``resilience``, the solve runs health-weighted (quotas follow
    per-rank throughput) and through the degradation ladder: a raised
    :class:`~repro.fault.injector.PlannerFault`, a deadline overrun, or a
    plan failing static verification falls back to the last-good cached
    plan, then to :func:`~repro.core.balancer.no_balance_plan` -- the stage
    never stalls the step.
    """
    cfg = ctx.cfg
    layout = cfg.layout
    home = layout.home()
    res = resilience
    health_weight = None if res is None else res.health_weight()

    def _solve():
        if res is not None and res.injector is not None:
            res.injector.check_solve(res.layer)
        t0 = time.monotonic()
        plan = balancer_mod.solve(gs.lam, home, cfg.balancer,
                                  lam_e_est=lam_e_est,
                                  rack_size=cfg.rack_size,
                                  health_weight=health_weight,
                                  demand_tiebreak=cfg.gating.rack_binding,
                                  gate_tier_tokens=gs.gate_tier_tokens)
        deadline = None if res is None else res.cfg.solve_deadline_s
        if deadline is not None and time.monotonic() - t0 > deadline:
            raise SolveTimeout(
                f"plan solve exceeded {deadline}s deadline")
        return plan

    if res is None:
        plan = _solve()
    else:
        plan = res.solve_with_ladder(_solve, gs.lam, home,
                                     cfg.balancer.n_slot, cfg.rack_size,
                                     gs.gate_tier_tokens)
    return PlanState(plan=plan, slot_of_all=physical_slot_of(layout, plan.x))


def distribute_stage(ctx: StageCtx, params, gs: GateState,
                     ps: PlanState) -> DistributeState:
    """Stream the replica slots' weights: ONE packed transfer for w1/w3/w2.

    Moves ``N_slot`` experts' rows per rank (``R*N_slot`` rows on the EP
    wire); the mains ride along by reference.
    """
    cfg = ctx.cfg
    w1r, w3r, w2r = materialize_replica_stack(
        stack_layer((params.w1, params.w3, params.w2), params.layer),
        ps.plan.x, gs.my, ctx.axis_name,
        n_chunks=cfg.distribute_chunks, racks=cfg.racks,
        wire_dtype=cfg.wire_dtype)
    return DistributeState(w1=params.w1, w3=params.w3, w2=params.w2,
                           w1r=w1r, w3r=w3r, w2r=w2r, layer=params.layer)


def _distribute_with_ladder(
    ctx: StageCtx, params, gs: GateState, ps: PlanState,
    res: Resilience | None,
) -> tuple[PlanState, DistributeState]:
    """Replica streaming under the ladder: retry transients, else downgrade.

    A transfer fault that survives the bounded retry budget downgrades the
    whole layer to :func:`~repro.core.balancer.no_balance_plan` -- a
    replica-free plan needs no transfer at all -- rather than dispatching
    tokens to replicas whose weights never arrived.  Injected replica
    corruption (``transfer_corrupt``) is applied to the streamed slots
    only; the resulting NaN outputs are caught by the combine-side screen.
    """
    if res is None:
        return ps, distribute_stage(ctx, params, gs, ps)
    cfg = ctx.cfg
    try:
        res.guard_transfer()
    except TransferFault:
        res.counters["fallback_plans"] += 1
        plan = balancer_mod.no_balance_plan(
            gs.lam, cfg.layout.home(), cfg.balancer.n_slot, cfg.rack_size,
            gs.gate_tier_tokens)
        ps = PlanState(plan=plan,
                       slot_of_all=physical_slot_of(cfg.layout, plan.x))
    dist = distribute_stage(ctx, params, gs, ps)
    if res.injector is not None:
        dist = dist._replace(
            w1r=res.injector.corrupt_replicas(dist.w1r, res.layer))
    return ps, dist


# --------------------------------------------------------------------------
# Per-chunk stages
# --------------------------------------------------------------------------


def dispatch_stage(ctx: StageCtx, x_chunk: jax.Array,
                   expert_ids: jax.Array, gs: GateState, ps: PlanState, *,
                   occ_offset: jax.Array | None = None) -> DispatchState:
    """Reroute one token chunk into this rank's slot buffers.

    Issues the chunk's forward wire (flat or two-hop all_to_all) -- under
    overlap the driver calls this for chunk *i+1* before ComputeStage runs
    on chunk *i*, which is the seam the pipelining lives on.
    """
    cfg = ctx.cfg
    layout = cfg.layout
    num_slots = layout.experts_per_rank + layout.n_slot
    zero = jnp.zeros((), _I32)
    # A share's pairs routed to experts held elsewhere (id -1) take no slot.
    routed = expert_ids >= 0 if cfg.gating.holds_share else None

    if cfg.dispatch_mode == "replicated":
        # Tokens identical on every EP rank (decode / exact-reference path):
        # item j of expert e is owned by the instance whose cumulative quota
        # covers j; this rank computes its share, outputs are psum-merged.
        rb = fused_replicated_bucket(
            x_chunk, expert_ids, ps.plan.cum_u, gs.my, ps.slot_of_all[gs.my],
            num_slots=num_slots, cap_slot=cfg.cap_slot,
            occ_offset=occ_offset, routed=routed,
        )
        return DispatchState(xs=rb.xs, valid=rb.valid, inverse=rb,
                             drops_dispatch=zero, drops_slot=rb.drops,
                             max_rows=expert_ids.size)

    # Single-sort permutation engine (repro.moe.permute): on a factored
    # mesh the same destination-major buffers ride the two-hop tiered
    # exchange; the count metadata rides both hops unchanged.  The
    # payload is wire-encoded BEFORE the first hop (quantization happens
    # once, at the source; the intra-rack scatter of the two-hop wire
    # moves the already-encoded bytes) and decoded only after bucketing.
    # Routing lives entirely in the count metadata, so token placement
    # is bit-identical across wire dtypes (DESIGN.md S12).
    disp = fused_dispatch(
        x_chunk, expert_ids, ps.plan.cum_q[gs.my], ps.slot_of_all,
        num_slots=num_slots, cap_pair=cfg.cap_pair, occ_offset=occ_offset,
        routed=routed,
    )
    recv_x = _exchange(ctx, encode_wire(disp.send_x, cfg.wire_dtype))
    recv_c = _exchange(ctx, disp.send_counts)
    xs, valid, meta, slot_drops = fused_bucket(
        recv_x, recv_c, num_slots=num_slots, cap_slot=cfg.cap_slot
    )
    xs_scale = None
    if cfg.wire_dtype == "int8" and cfg.ffn_dtype == "int8":
        # End-to-end quantized: hand ComputeStage the codes + scales.
        xs, xs_scale = split_wire_int8(xs)
    else:
        xs = decode_wire(xs, cfg.wire_dtype, x_chunk.dtype)
    return DispatchState(xs=xs, valid=valid, inverse=(disp, meta),
                         drops_dispatch=disp.drops, drops_slot=slot_drops,
                         xs_scale=xs_scale,
                         max_rows=recv_x.shape[0] * recv_x.shape[1])


def compute_stage(ctx: StageCtx, ds: DispatchState, dist: DistributeState
                  ) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """Grouped FFN over this rank's physical slots for one chunk.

    The main slots run against the mains and the replica slots against the
    streamed replicas: the same per-slot SwiGLU, with no (num_slots, D, F)
    weight stack built to feed it.  Returns the slot outputs as the two
    consecutive slot ranges ``(mains, replicas)``, each
    ``(slots, cap_slot, D)``, which CombineStage reads without
    concatenating, and the rows the FFN ran (int32).
    """
    cfg = ctx.cfg
    n_main = dist.w1.shape[-3]

    def ffn(lo, hi, w1, w3, w2, layer=None):
        return grouped_ffn(
            ds.xs[lo:hi], ds.valid[lo:hi], w1, w3, w2,
            use_kernel=cfg.use_kernel, ffn_dtype=cfg.ffn_dtype,
            xs_scale=None if ds.xs_scale is None else ds.xs_scale[lo:hi],
            max_rows=ds.max_rows, layer=layer)

    out_m, rows_m = ffn(0, n_main, dist.w1, dist.w3, dist.w2, dist.layer)
    out_r, rows_r = ffn(n_main, None, dist.w1r, dist.w3r, dist.w2r)
    return (out_m, out_r), rows_m + rows_r


def combine_stage(ctx: StageCtx, ds: DispatchState,
                  outs: tuple[jax.Array, ...],
                  weights: jax.Array) -> jax.Array:
    """Route FFN outputs back and reduce each token's k contributions.

    ``outs`` is ComputeStage's slot outputs; ``weights`` is the (T_chunk, k)
    gate-weight slice of this chunk; the return is the chunk's (T_chunk, D)
    combined output (pre-psum for the replicated mode -- run_staged_moe merges
    ranks once over the whole batch).  Both modes gather from the slot
    ranges as they are, without concatenating them.
    """
    cfg = ctx.cfg
    if cfg.dispatch_mode == "replicated":
        return fused_replicated_combine(outs, ds.inverse, weights)
    # The return wire carries the same codec as the forward wire: FFN
    # outputs are encoded per-row before the reverse exchange and decoded
    # at the source rank, right before the weighted reduce.
    disp, meta = ds.inverse
    ret = _exchange(ctx, encode_wire(fused_unbucket(outs, meta),
                                     cfg.wire_dtype), reverse=True)
    return fused_combine(decode_wire(ret, cfg.wire_dtype, outs[0].dtype),
                         disp, weights)


# --------------------------------------------------------------------------
# Chunking helpers
# --------------------------------------------------------------------------


def chunk_bounds(total: int, *, n_chunks: int | None = None,
                 chunk_size: int | None = None) -> list[tuple[int, int]]:
    """(start, length) spans covering ``[0, total)``, in order.

    Exactly one of ``n_chunks`` (equal split; must divide ``total``) or
    ``chunk_size`` (fixed-size spans, ragged tail allowed) must be given.
    Shared by the overlap driver (equal chunks of the microbatch) and the
    serving engine's chunked prefill (fixed chunk, ragged last span).
    """
    if (n_chunks is None) == (chunk_size is None):
        raise ValueError("pass exactly one of n_chunks / chunk_size")
    if n_chunks is not None:
        if n_chunks < 1 or total % n_chunks != 0:
            raise ValueError(
                f"n_chunks={n_chunks} must be >= 1 and divide total={total}")
        size = total // n_chunks
        return [(i * size, size) for i in range(n_chunks)]
    if chunk_size < 1:
        raise ValueError(f"chunk_size={chunk_size} must be >= 1")
    return [(s, min(chunk_size, total - s)) for s in range(0, total, chunk_size)]


def chunk_occ_offsets(expert_ids: jax.Array, n_chunks: int,
                      num_experts: int) -> jax.Array:
    """(C, E) per-chunk occurrence offsets continuing the global index.

    Chunk c's offset for expert e is the number of e-items in chunks < c
    (exclusive cumsum of per-chunk expert histograms).  Adding it to each
    chunk's local occurrence index makes ``occ`` globally consistent with
    the unchunked dispatch, so every item hits the exact same expert
    instance under the shared quota tables -- the mechanism behind chunked
    == unchunked bit-identity (module docstring).
    """
    ec = expert_ids.reshape(n_chunks, -1).astype(_I32)       # (C, Tc*k)
    oh = ec[:, :, None] == jnp.arange(num_experts, dtype=_I32)[None, None, :]
    hist = oh.astype(_I32).sum(axis=1)                       # (C, E)
    return jnp.cumsum(hist, axis=0) - hist                   # exclusive


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def run_staged_moe(
    x: jax.Array,
    params,
    cfg,
    *,
    axis_name: str | tuple[str, str] | None,
    router_bias: jax.Array | None = None,
    lam_e_est: jax.Array | None = None,
    resilience: Resilience | None = None,
) -> tuple[jax.Array, jax.Array, MoEStats]:
    """One balanced MoE layer as a staged, optionally chunk-overlapped run.

    gate -> plan -> distribute execute once on the full microbatch; the
    dispatch -> compute -> combine tail runs per overlap chunk, software-
    pipelined so chunk i+1's dispatch (and its all_to_all) is issued before
    chunk i's FFN + combine -- under XLA's latency-hiding scheduler the
    wire of the next chunk overlaps the compute of the current one.

    With ``resilience`` (DESIGN.md S13) the layer runs degraded-fabric
    hardened: the plan solve is health-weighted and falls down the
    degradation ladder instead of raising; replica streaming retries
    transient faults and downgrades to a replica-free plan on exhaustion;
    dispatched payloads and combined outputs are screened for NaN/Inf rows
    at the stage boundaries (corrupted rows dropped + counted, never
    propagated to the residual stream); and the new ``MoEStats`` fault
    counters report what happened.

    Each stage runs under a named scope (``moe.gate``, ``moe.plan``,
    ``moe.distribute``, ``moe.dispatch``, ``moe.ffn``, ``moe.combine``,
    ``moe.shared``), so a profile puts the device's time down to a stage.
    """
    T, D = x.shape
    ctx = make_stage_ctx(cfg, axis_name)
    res = resilience
    fallback_before = (0 if res is None
                       else res.counters["fallback_plans"])
    with jax.named_scope("moe.gate"):
        gs = gate_stage(ctx, x, params.router, router_bias)
    with jax.named_scope("moe.plan"):
        ps = plan_stage(ctx, gs, lam_e_est=lam_e_est, resilience=res)
    with jax.named_scope("moe.distribute"):
        ps, dist = _distribute_with_ladder(ctx, params, gs, ps, res)

    C = cfg.overlap_chunks
    if T % C != 0:
        raise ValueError(
            f"overlap_chunks={C} must divide the local token count T={T}")
    bounds = chunk_bounds(T, n_chunks=C)
    with jax.named_scope("moe.dispatch"):
        offsets = (chunk_occ_offsets(gs.gate_out.expert_ids, C,
                                     cfg.layout.num_experts)
                   if C > 1 else None)
    screening = res is not None and res.cfg.screen_payloads

    @jax.named_scope("moe.dispatch")
    def disp(i: int) -> DispatchState:
        s, ln = bounds[i]
        off = offsets[i] if offsets is not None else None
        d = dispatch_stage(ctx, x[s:s + ln],
                           gs.gate_out.expert_ids[s:s + ln], gs, ps,
                           occ_offset=off)
        if res is not None and res.injector is not None:
            d = d._replace(xs=res.injector.corrupt_payload(d.xs, res.layer))
        return d

    ys = []
    drops_dispatch = jnp.zeros((), _I32)
    drops_slot = jnp.zeros((), _I32)
    max_slot_load = jnp.zeros((), _I32)
    rows_run = jnp.zeros((), _I32)
    dropped_payload = jnp.zeros((), _I32)
    d_next = disp(0)
    for i in range(C):
        # Double-buffer: issue chunk i+1's dispatch before consuming chunk
        # i's buffers, then retire chunk i with FFN + combine.
        d_cur, d_next = d_next, (disp(i + 1) if i + 1 < C else None)
        if screening:
            with jax.named_scope("moe.dispatch"):
                xs, valid, n_bad = screen_payload(d_cur.xs, d_cur.valid)
            d_cur = d_cur._replace(xs=xs, valid=valid)
            dropped_payload = dropped_payload + n_bad
        with jax.named_scope("moe.ffn"):
            outs, rows = compute_stage(ctx, d_cur, dist)
        rows_run = rows_run + rows
        s, ln = bounds[i]
        with jax.named_scope("moe.combine"):
            y_chunk = combine_stage(ctx, d_cur, outs,
                                    gs.gate_out.weights[s:s + ln])
            if screening:
                y_chunk, n_bad = _screen_rows(y_chunk)
                dropped_payload = dropped_payload + n_bad
        ys.append(y_chunk)
        drops_dispatch = drops_dispatch + d_cur.drops_dispatch
        drops_slot = drops_slot + d_cur.drops_slot
        max_slot_load = jnp.maximum(
            max_slot_load, d_cur.valid.sum(axis=1).max().astype(_I32))
    with jax.named_scope("moe.combine"):
        y = ys[0] if C == 1 else jnp.concatenate(ys, axis=0)
        if cfg.dispatch_mode == "replicated":
            # One rank-merge over the whole batch: psum is elementwise, so
            # the merged concat equals the concat of per-chunk merges
            # bitwise.
            if ctx.factored:
                y = jax.lax.psum(jax.lax.psum(y, ctx.lane_axis),
                                 ctx.rack_axis)
            elif ctx.axis_name is not None:
                y = jax.lax.psum(y, ctx.axis_name)

    if cfg.n_shared_experts > 0:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(x, params.shared_w1, params.shared_w3,
                           params.shared_w2)

    tier_bytes = None
    if ps.plan.tier_tokens is not None:
        # One-way dispatch-wire bytes per tier: the item count times the
        # wire payload width (base width = the activation dtype; int8 adds
        # 4 in-band scale bytes per row).  Shares its width definition with
        # the host cost model and the static verifier via repro.core.quantize.
        tier_bytes = ps.plan.tier_tokens * payload_bytes_per_item(
            D, cfg.wire_dtype, base_bytes=x.dtype.itemsize)

    fallbacks = quarantined = None
    if res is not None:
        fallbacks = jnp.asarray(
            res.counters["fallback_plans"] - fallback_before, _I32)
        quarantined = jnp.asarray(res.num_quarantined(), _I32)
    stats = MoEStats(
        drops_dispatch=drops_dispatch,
        drops_slot=drops_slot,
        pre_max=ps.plan.pre_max,
        post_max=ps.plan.post_max,
        max_slot_load=max_slot_load,
        counts=gs.gate_out.counts,
        tier_tokens=ps.plan.tier_tokens,
        tier_replicas=ps.plan.tier_replicas,
        tier_bytes=tier_bytes,
        gate_tier_tokens=ps.plan.gate_tier_tokens,
        fallback_plans=fallbacks,
        dropped_payload_tokens=(dropped_payload if res is not None else None),
        quarantined_ranks=quarantined,
        rows_run=rows_run,
    )
    return y.astype(x.dtype), gs.gate_out.aux_loss, stats
