"""Balanced MoE layer: the paper's Fig. 8 forward pipeline on TPU.

Per EP rank (inside ``shard_map`` over the EP axis), one MoE layer executes:

  gate -> all_gather(counts) = exact load  ->  solve plan (device-resident)
       -> [ materialize replica weights  ||  reroute items ]
       -> token all_to_all -> grouped FFN over physical slots
       -> inverse all_to_all -> weighted combine (+ shared experts)

The execution itself lives in :mod:`repro.moe.stages` as six typed stages
(gate/plan/distribute/dispatch/compute/combine, DESIGN.md S11);
:func:`moe_layer_local` is the public entry point that owns the config and
parameter containers and delegates to the staged driver.  With
``overlap_chunks > 1`` the dispatch->compute->combine tail is software-
pipelined over token chunks sharing one plan, hiding the all_to_all under
the grouped FFN while staying bit-identical at zero-drop capacities.

Backward is derived by ``jax.grad``: the replica-weight collective transposes
into the replica-gradient reduction onto mains (S4.2), and a
``jax.checkpoint`` policy re-materialises replica weights instead of saving
them (the paper's cross-layer redundant-buffer reuse).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.balancer import BalancerConfig
from repro.core.layout import ExpertLayout
from repro.moe.gating import GatingConfig
from repro.moe.stages import MoEStats, run_staged_moe

__all__ = ["MoEConfig", "MoEParams", "MoEStats", "moe_layer_local",
           "init_moe_params", "default_capacities"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    gating: GatingConfig
    balancer: BalancerConfig
    d_model: int
    d_ff: int                      # per-expert hidden size
    ep_size: int                   # R (EP group = model-axis size)
    cap_pair: int                  # tokens per (src,dst) pair buffer
    cap_slot: int                  # tokens per physical expert slot
    n_shared_experts: int = 0      # DeepSeek shared (always-on) experts
    shared_d_ff: int = 0
    distribute_chunks: int = 1     # tile-streaming chunk knob
    overlap_chunks: int = 1        # dispatch/compute overlap: token chunks
    # sharing ONE plan, software-pipelined so chunk i+1's all_to_all runs
    # under chunk i's grouped FFN (repro.moe.stages; DESIGN.md S11).
    # Bit-identical to unchunked at zero-drop capacities; must divide the
    # local token count at call time.
    use_kernel: bool = False       # expert FFN's Pallas kernels off the
    # TPU too (interpret mode), and the w8a8 path's; the TPU runs the fp
    # kernel regardless
    dispatch_mode: str = "a2a"     # "a2a" | "replicated" | "hier_a2a"
    # "replicated": tokens are replicated across the EP axis (decode path /
    # exact reference); each rank computes the quota-assigned share of items
    # for its hosted slots and the outputs are psum-combined.  No token
    # all_to_all, no pair capacities, no drops at pair granularity.
    # "hier_a2a": two-level (rack x lane) EP -- the rack-aware plan solve,
    # the two-hop token exchange and the tiered replica streaming of
    # DESIGN.md S9.  Requires a factored (rack_axis, lane_axis) mesh;
    # bit-identical to "a2a" on one rack.
    racks: int = 1                 # racks of the two-level EP group
    wire_dtype: str = "none"       # EP-wire payload codec (DESIGN.md S12):
    # "none" (native dtype, bit-exact oracle path) | "bf16" | "int8"
    # (per-row symmetric, fp32 scales packed in-band).  Covers the token
    # all_to_all (both directions) and the replica weight stream; routing,
    # counts and slot placement are computed BEFORE encoding and are
    # bit-identical across wire dtypes.
    ffn_dtype: str = "none"        # expert FFN compute dtype: "none" (fp
    # reference, default) | "int8" (w8a8 grouped SwiGLU, per-token-row
    # activation scales x per-(expert, out-feature) weight scales).  With
    # wire_dtype == "int8" the slot buffers feed the kernel still encoded
    # (no dequant round-trip).

    def __post_init__(self):
        # Fail at construction, not at trace time (DESIGN.md S9).
        if self.dispatch_mode not in ("a2a", "replicated", "hier_a2a"):
            raise ValueError(f"unknown dispatch_mode: {self.dispatch_mode!r}")
        if self.racks < 1 or self.ep_size % self.racks != 0:
            raise ValueError(
                f"racks={self.racks} must divide ep_size={self.ep_size}")
        if self.distribute_chunks < 1:
            raise ValueError(
                f"distribute_chunks={self.distribute_chunks} must be >= 1")
        if self.overlap_chunks < 1:
            raise ValueError(
                f"overlap_chunks={self.overlap_chunks} must be >= 1")
        if self.wire_dtype not in ("none", "bf16", "int8"):
            raise ValueError(f"unknown wire_dtype: {self.wire_dtype!r}")
        if self.ffn_dtype not in ("none", "int8"):
            raise ValueError(f"unknown ffn_dtype: {self.ffn_dtype!r}")
        if self.gating.holds_share and self.racks != 1:
            raise ValueError("an expert share runs flat EP (racks=1)")

    @property
    def ranks_per_rack(self) -> int:
        return self.ep_size // self.racks

    @property
    def rack_size(self) -> int | None:
        """Ranks per rack when the topology is two-level, else None (flat)."""
        return self.ranks_per_rack if self.racks > 1 else None

    @property
    def layout(self) -> ExpertLayout:
        return ExpertLayout(self.gating.held, self.ep_size,
                            self.balancer.n_slot)


class MoEParams(NamedTuple):
    router: jax.Array        # (D, E) fp32 router projection, E the router's
                             #   width (a share holds fewer experts)
    w1: jax.Array            # (E_local, D, F) gate proj (per-rank shard)
    w3: jax.Array            # (E_local, D, F) up proj
    w2: jax.Array            # (E_local, F, D) down proj
    shared_w1: jax.Array | None = None   # (D, F_sh)
    shared_w3: jax.Array | None = None
    shared_w2: jax.Array | None = None   # (F_sh, D)
    layer: jax.Array | None = None  # () int32: w1/w3/w2 are a scanned
                             #   segment's whole (L, E_local, ...) stacks and
                             #   this layer's experts sit at this index
                             #   (read in place, never sliced out); None
                             #   when they are one layer's


def default_capacities(tokens_per_rank: int, top_k: int, ep_size: int,
                       slots_per_rank: int, *, cf_pair: float = 2.0,
                       cf_slot: float = 2.0,
                       topology=None) -> tuple[int, int]:
    """Static capacity bounds sized off the balanced expectation.

    Balanced dispatch sends ~T*k/R items per (src,dst) pair and lands ~T*k
    items per rank spread over its physical slots; the capacity factor is the
    safety margin for residual imbalance.  Unbalanced runs need cf ~= the
    pre-balance imbalance ratio (1.3-4x per the paper) -- this is exactly how
    balancing shows up as memory savings (Fig. 14).

    ``topology`` (a :class:`repro.core.topology.Topology`) switches on the
    rack-aware pair bound.  The rack-local reroute tier deliberately
    *concentrates* a source rank's traffic onto in-rack destinations, so per
    (src, dst) pair traffic is no longer ~items/ep_size: the static analysis
    layer showed skewed rack-aware solves exceeding the flat bound by >2x
    (silent drops at dispatch).  The per-rack aggregate bound sizes the pair
    buffer for all of a source's traffic to one *rack* landing on a single
    rank: ``ceil(items * cf_pair / racks)``.  Flat topologies (racks == 1)
    are unchanged.
    """
    items = tokens_per_rank * top_k
    if topology is not None and topology.racks > 1:
        cap_pair = max(8, int(-(-items * cf_pair // topology.racks)))
    else:
        cap_pair = max(8, int(-(-items * cf_pair // ep_size)))
    cap_slot = max(8, int(-(-items * cf_slot // slots_per_rank)))
    return cap_pair, cap_slot


def init_moe_params(key: jax.Array, cfg: MoEConfig,
                    dtype=jnp.float32) -> MoEParams:
    """Per-rank parameter shard (E_local experts of the held block; the
    router spans all of the router's experts)."""
    E = cfg.gating.num_experts
    epr = cfg.layout.experts_per_rank
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 7)
    scale_in = D ** -0.5
    scale_out = F ** -0.5
    shared = [None, None, None]
    if cfg.n_shared_experts > 0:
        Fs = cfg.shared_d_ff * cfg.n_shared_experts
        shared = [
            (jax.random.normal(ks[4], (D, Fs), dtype) * scale_in),
            (jax.random.normal(ks[5], (D, Fs), dtype) * scale_in),
            (jax.random.normal(ks[6], (Fs, D), dtype) * scale_out),
        ]
    return MoEParams(
        router=jax.random.normal(ks[0], (D, E), jnp.float32) * scale_in,
        w1=jax.random.normal(ks[1], (epr, D, F), dtype) * scale_in,
        w3=jax.random.normal(ks[2], (epr, D, F), dtype) * scale_in,
        w2=jax.random.normal(ks[3], (epr, F, D), dtype) * scale_out,
        shared_w1=shared[0], shared_w3=shared[1], shared_w2=shared[2],
    )


def moe_layer_local(
    x: jax.Array,
    params: MoEParams,
    cfg: MoEConfig,
    *,
    axis_name: str | tuple[str, str] | None,
    router_bias: jax.Array | None = None,
    lam_e_est: jax.Array | None = None,
    resilience=None,
) -> tuple[jax.Array, jax.Array, MoEStats]:
    """One balanced MoE layer, per-rank view (call under shard_map).

    Thin wrapper over :func:`repro.moe.stages.run_staged_moe` -- the staged
    driver composes gate/plan/distribute (once per microbatch) with the
    per-chunk dispatch/compute/combine tail according to
    ``cfg.dispatch_mode`` and ``cfg.overlap_chunks``.

    Args:
      x: (T_local, D) this rank's tokens.
      params: per-rank parameter shard.
      axis_name: EP mesh axis; a ``(rack_axis, lane_axis)`` tuple for a
        factored two-level mesh (required by ``dispatch_mode="hier_a2a"``
        with ep_size > 1, supported by "replicated"); None = single-rank
        (R must be 1).
      router_bias: optional (E,) aux-free routing bias.
      lam_e_est: optional stale per-expert load estimate (EPLB mode).
      resilience: optional :class:`repro.moe.stages.Resilience` -- health-
        weighted planning, the degradation ladder, and payload screening
        (DESIGN.md S13).

    Returns:
      (y, aux_loss, stats) with y: (T_local, D).
    """
    return run_staged_moe(x, params, cfg, axis_name=axis_name,
                          router_bias=router_bias, lam_e_est=lam_e_est,
                          resilience=resilience)
