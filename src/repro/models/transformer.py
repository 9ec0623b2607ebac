"""Transformer assembly: heterogeneous block patterns over a shared residual.

Layers are grouped into *segments* of consecutive identical block kinds
(``attn+dense``, ``attn+moe``, ``mamba+dense``, ``mamba+moe``, ``mamba+none``,
``attn+none``); each segment's parameters are stacked on a leading axis and
executed with ``lax.scan`` (+ per-layer ``jax.checkpoint``), which keeps HLO
size O(#kinds) instead of O(#layers) -- essential for 80-90-layer dry-runs at
512 partitions.  Heterogeneous cycles (jamba) degrade gracefully to short
segments.

MoE blocks are ``shard_map`` islands over the EP ("model") axis inside the
otherwise-pjit graph; everything else relies on GSPMD propagation from the
parameter/activation shardings in :mod:`repro.parallel.sharding`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.balancer import BalancerConfig
from repro.core.topology import Topology
from repro.configs.base import ModelConfig, layer_kinds
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import AttnConfig, GQAParams, KVCache, MLAParams
from repro.models.layers import dense_swiglu, rms_norm
from repro.models.ssm import SSMConfig, SSMParams, SSMState
from repro.moe.gating import GatingConfig
from repro.moe.layer import (
    MoEConfig,
    MoEParams,
    default_capacities,
    init_moe_params,
    moe_layer_local,
)

__all__ = ["RuntimeConfig", "ParallelCtx", "BlockParams", "Segment",
           "build_segments", "segments_for", "segment_apply", "attn_config",
           "ssm_config", "moe_config", "block_moe_config", "moe_slot_rows",
           "effective_rack_limit", "init_block", "init_cache_block"]


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Execution knobs orthogonal to the architecture."""

    balancer: BalancerConfig = BalancerConfig()
    cf_pair: float = 2.0
    cf_slot: float = 2.0
    distribute_chunks: int = 1
    overlap_chunks: int = 1        # MoE dispatch/compute overlap chunks
    # (repro.moe.stages); falls back to 1 per layer when the local token
    # count is not divisible.
    use_kernel: bool = False
    wire_dtype: str = "none"       # EP wire codec: "none" | "bf16" | "int8"
    # (repro.core.quantize, DESIGN.md S12).
    ffn_dtype: str = "none"        # expert FFN compute: "none" | "int8" (w8a8)
    rack_limit: int = 0            # bound each token's experts to this many
    # racks at the gate (0 = free routing, DESIGN.md S14); degrades to free
    # routing on flat/single-rack meshes and whenever the limit would expose
    # fewer than top_k experts (see effective_rack_limit).
    block_kv: int = 512
    dtype: Any = jnp.float32
    remat: bool = True
    scan_layers: bool = True
    min_scan_len: int = 2          # don't scan segments shorter than this
    scan_cycles: bool = True       # scan heterogeneous repeating periods
    loss_chunks: int = 1           # >1: blocked CE, no (B,S,V) materialise
    analysis_unroll: bool = False  # unroll inner scans for exact cost_analysis


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Mesh context for the shard_map MoE islands; None mesh = single device.

    ``rack_axis`` factors the EP group into a two-level (rack x lane)
    topology: the model axis becomes the intra-rack lane dimension and EP
    collectives become tiered (DESIGN.md S9).  Global EP rank order is
    rack-major, so flat and factored meshes agree on rank numbering.
    """

    mesh: Any = None                     # jax.sharding.Mesh
    batch_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    rack_axis: str | None = None         # scale-out EP axis (None = flat EP)

    @property
    def ep_axes(self) -> str | tuple[str, str]:
        """Mesh axes of the EP group: (rack, lane) when factored."""
        if self.rack_axis is not None:
            return (self.rack_axis, self.model_axis)
        return self.model_axis

    @property
    def racks(self) -> int:
        if self.mesh is None or self.rack_axis is None:
            return 1
        return int(self.mesh.shape[self.rack_axis])

    @property
    def ep_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.racks * int(self.mesh.shape[self.model_axis])

    @property
    def batch_size_divisor(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))


def wsc(x: jax.Array, pctx: ParallelCtx, layout: str, *,
        decode: bool = False) -> jax.Array:
    """Activation sharding constraint (sequence-parallel residual stream).

    layout: "seq"  -- (B->batch axes, S->model, D) between blocks;
            "full" -- (B->batch axes, S, D) gathered sequence inside mixers
            (Megatron sequence parallelism: gather at mixer entry,
            reduce-scatter back at exit).
    Decode steps (S=1) never shard the sequence.
    """
    if pctx.mesh is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    b, m = pctx.batch_axes, pctx.ep_axes
    if x.shape[0] % pctx.batch_size_divisor != 0:
        b = None                      # tiny batch (long_500k): replicate B
    seq = None if (decode or layout == "full") else m
    if x.ndim > 1 and seq is not None and x.shape[1] % pctx.ep_size != 0:
        seq = None
    spec = P(b, seq, *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pctx.mesh, spec))


class BlockParams(NamedTuple):
    norm1: jax.Array
    norm2: jax.Array | None
    attn: GQAParams | MLAParams | None
    ssm: SSMParams | None
    ffn: tuple[jax.Array, jax.Array, jax.Array] | None
    moe: MoEParams | None


class Segment(NamedTuple):
    kind: str               # e.g. "attn+moe"; "cycle" = heterogeneous period
    length: int             # number of layers
    layer_ids: tuple[int, ...]
    cycle: tuple[str, ...] = ()   # per-position kinds when kind == "cycle"

    @property
    def n_cycles(self) -> int:
        return self.length // max(len(self.cycle), 1)


def attn_config(cfg: ModelConfig) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        causal=cfg.causal, qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
    )


def ssm_config(cfg: ModelConfig) -> SSMConfig:
    s = cfg.ssm
    return SSMConfig(d_model=cfg.d_model, d_inner=s.d_inner,
                     headdim=s.headdim, d_state=s.d_state,
                     n_groups=s.n_groups, d_conv=s.d_conv, chunk=s.chunk)


def effective_rack_limit(m, rcfg: RuntimeConfig, racks: int) -> int:
    """The gate rack limit actually applied, with safe degradation.

    ``rcfg.rack_limit`` is a deployment knob; it silently degrades to free
    routing (0) whenever the topology or architecture cannot honor it: a
    flat or single-rack mesh has no inter-rack tier to bound, experts that
    do not divide evenly into racks break the rack-blocked layout the mask
    assumes, and a limit exposing fewer than ``top_k`` experts could not
    route at all.  Clamped to the rack count otherwise.
    """
    if rcfg.rack_limit <= 0 or racks <= 1 or m is None:
        return 0
    if m.num_experts % racks != 0:
        return 0
    limit = min(rcfg.rack_limit, racks)
    if limit * (m.num_experts // racks) < m.top_k:
        return 0
    return limit


def moe_config(cfg: ModelConfig, rcfg: RuntimeConfig, pctx: ParallelCtx,
               tokens_per_rank: int, *, dispatch_mode: str = "a2a",
               ideal: bool = False) -> MoEConfig:
    m = cfg.moe
    ep = pctx.ep_size
    rack_limit = effective_rack_limit(m, rcfg, pctx.racks)
    gating = GatingConfig(
        num_experts=m.num_experts, top_k=m.top_k, score_fn=m.score_fn,
        norm_topk_prob=m.norm_topk_prob, aux_loss_weight=m.aux_loss_weight,
        routed_scaling=m.routed_scaling, use_bias=m.use_bias,
        bias_update_speed=m.bias_update_speed,
        ideal=ideal or rcfg.balancer.mode == "ideal",
        rack_limit=rack_limit,
        num_racks=pctx.racks if rack_limit else 1,
        held_experts=m.held_experts, first_expert=m.first_expert,
    )
    bal = dataclasses.replace(rcfg.balancer, n_slot=m.n_slot)
    slots_per_rank = m.held // ep + m.n_slot
    # Factored mesh: size pair buffers with the per-rack aggregate bound --
    # the rack-local reroute tier concentrates a source's traffic in-rack,
    # so the flat ~items/ep_size expectation under-provisions (silent drops).
    topo = (Topology(racks=pctx.racks, ranks_per_rack=ep // pctx.racks)
            if pctx.rack_axis is not None and pctx.racks > 1 else None)
    cap_pair, cap_slot = default_capacities(
        tokens_per_rank, m.top_k, ep, slots_per_rank,
        cf_pair=rcfg.cf_pair, cf_slot=rcfg.cf_slot, topology=topo,
    )
    if pctx.rack_axis is not None and dispatch_mode == "a2a":
        dispatch_mode = "hier_a2a"   # factored mesh: tiered token exchange
    # Overlap chunking must divide the per-rank token count; rather than
    # fail deep inside a scanned block, degrade to unchunked here.
    overlap = rcfg.overlap_chunks
    if overlap >= 1 and tokens_per_rank % overlap != 0:
        overlap = 1   # overlap < 1 passes through to MoEConfig's validation
    return MoEConfig(
        gating=gating, balancer=bal, d_model=cfg.d_model, d_ff=m.d_ff,
        ep_size=ep, cap_pair=cap_pair, cap_slot=cap_slot,
        n_shared_experts=m.n_shared_experts, shared_d_ff=m.shared_d_ff,
        distribute_chunks=rcfg.distribute_chunks, overlap_chunks=overlap,
        use_kernel=rcfg.use_kernel,
        dispatch_mode=dispatch_mode,
        racks=pctx.racks,
        wire_dtype=rcfg.wire_dtype, ffn_dtype=rcfg.ffn_dtype,
    )


def block_moe_config(cfg: ModelConfig, rcfg: RuntimeConfig,
                     pctx: ParallelCtx, batch: int, seq: int, *,
                     decode: bool = False) -> MoEConfig:
    """The MoEConfig a block's MoE layer runs with on a (batch, seq) input:
    decode replicates its tokens over the EP group, every other mode
    shards the sequence over it."""
    tokens_per_rank = max(
        1, (batch // pctx.batch_size_divisor)
        * (seq if decode or seq < pctx.ep_size else seq // pctx.ep_size))
    return moe_config(cfg, rcfg, pctx, tokens_per_rank,
                      dispatch_mode="replicated" if decode else "a2a")


def moe_slot_rows(cfg: ModelConfig, rcfg: RuntimeConfig, pctx: ParallelCtx,
                  batch: int, seq: int, *, decode: bool = False) -> int:
    """Capacity rows of one MoE layer's expert slots on a (batch, seq)
    input, summed over the devices: each holds ``overlap_chunks`` x
    ``num_slots`` x ``cap_slot`` rows, whether a routed pair fills a row
    or not.  The dense FFN runs them all; the count-aware kernel only the
    filled row tiles (``MoECounters.rows_run``)."""
    m = block_moe_config(cfg, rcfg, pctx, batch, seq, decode=decode)
    devices = 1 if pctx.mesh is None else (pctx.batch_size_divisor
                                           * pctx.ep_size)
    slots = m.layout.experts_per_rank + m.layout.n_slot
    return devices * m.overlap_chunks * slots * m.cap_slot


def _pattern_period(cfg: ModelConfig) -> tuple[int, int]:
    """(prefix, period) of the layer-kind pattern."""
    import math

    p = 1
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.layer_period)
    if cfg.ssm is not None and cfg.ssm.attn_period:
        p = math.lcm(p, cfg.ssm.attn_period)
    pre = cfg.moe.first_dense_layers if cfg.moe is not None else 0
    return pre, p


def build_segments(cfg: ModelConfig, *, scan_cycles: bool = True
                   ) -> list[Segment]:
    """Group layers into scannable segments.

    Homogeneous runs scan directly.  Heterogeneous repeating patterns
    (jamba's 8-layer mamba/attn/moe cycle) become ONE "cycle" segment that
    scans over period repetitions with the period unrolled inside the body
    -- keeping HLO size O(period) instead of O(num_layers) and letting
    per-layer remat apply (a ~10x compile-time/memory win on jamba,
    EXPERIMENTS.md SPerf).
    """
    kinds = layer_kinds(cfg)
    pre, p = _pattern_period(cfg)
    n_rep = (len(kinds) - pre) // p if p > 1 else 0
    segs: list[Segment] = []
    if (scan_cycles and p > 1 and n_rep >= 2
            and pre + n_rep * p == len(kinds)
            and all(kinds[pre + i] == kinds[pre + (i % p)]
                    for i in range(n_rep * p))):
        # prefix as plain segments
        start = 0
        for i in range(1, pre + 1):
            if i == pre or kinds[i] != kinds[start]:
                segs.append(Segment(kinds[start], i - start,
                                    tuple(range(start, i))))
                start = i
        segs.append(Segment("cycle", n_rep * p,
                            tuple(range(pre, len(kinds))),
                            cycle=tuple(kinds[pre:pre + p])))
        return segs
    start = 0
    for i in range(1, len(kinds) + 1):
        if i == len(kinds) or kinds[i] != kinds[start]:
            segs.append(Segment(kinds[start], i - start,
                                tuple(range(start, i))))
            start = i
    return segs


def segments_for(cfg: ModelConfig, rcfg: RuntimeConfig) -> list[Segment]:
    return build_segments(
        cfg, scan_cycles=rcfg.scan_cycles and rcfg.scan_layers
        and not rcfg.analysis_unroll)


# --------------------------------------------------------------------------
# Parameter init
# --------------------------------------------------------------------------

def init_block(key: jax.Array, cfg: ModelConfig, kind: str,
               rcfg: RuntimeConfig, pctx: ParallelCtx) -> BlockParams:
    mixer, ffn_kind = kind.split("+")
    D = cfg.d_model
    dtype = rcfg.dtype
    ks = jax.random.split(key, 4)
    attn = ssm = ffn = moe = None
    if mixer == "attn":
        acfg = attn_config(cfg)
        attn = (attn_mod.init_mla(ks[0], acfg, dtype) if cfg.is_mla
                else attn_mod.init_gqa(ks[0], acfg, dtype))
    else:
        ssm = ssm_mod.init_ssm(ks[0], ssm_config(cfg), dtype)
    if ffn_kind == "dense":
        F = cfg.d_ff
        k1, k2, k3 = jax.random.split(ks[1], 3)
        ffn = (
            jax.random.normal(k1, (D, F), dtype) * D ** -0.5,
            jax.random.normal(k2, (D, F), dtype) * D ** -0.5,
            jax.random.normal(k3, (F, D), dtype) * F ** -0.5,
        )
    elif ffn_kind == "moe":
        # Parameters are GLOBAL (all E experts the chip's EP group holds:
        # the router's width, or an expert share's block); the shard_map
        # in_specs split the expert dim over the EP axis at execution time.  The
        # single-group init view must also collapse the rack factoring
        # (racks must divide ep_size).
        mcfg = moe_config(cfg, rcfg, pctx, tokens_per_rank=8)  # caps unused
        moe = init_moe_params(
            ks[1],
            dataclasses.replace(mcfg, ep_size=1, racks=1,
                                dispatch_mode="a2a"),
            dtype)
    norm2 = None if ffn_kind == "none" else jnp.ones((D,), dtype)
    return BlockParams(norm1=jnp.ones((D,), dtype), norm2=norm2,
                       attn=attn, ssm=ssm, ffn=ffn, moe=moe)


def init_cache_block(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     dtype) -> Any:
    """Decode cache entry for one layer (KVCache / SSMState / None)."""
    mixer, _ = kind.split("+")
    if mixer == "attn":
        if cfg.is_mla:
            return KVCache(
                k=jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
                v=jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype),
                length=jnp.zeros((batch,), jnp.int32),
            )
        return KVCache(
            k=jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim),
                        dtype),
            v=jnp.zeros((batch, max_seq, cfg.num_kv_heads, cfg.head_dim),
                        dtype),
            length=jnp.zeros((batch,), jnp.int32),
        )
    scfg = ssm_config(cfg)
    return SSMState(
        s=jnp.zeros((batch, scfg.n_heads, scfg.d_state, scfg.headdim),
                    jnp.float32),
        conv=jnp.zeros((batch, scfg.d_conv - 1,
                        ssm_mod._conv_channels(scfg)), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _ep_moe_block(x: jax.Array, mp: MoEParams, mcfg: MoEConfig,
                  pctx: ParallelCtx, router_bias: jax.Array | None):
    """shard_map island: (B, S, D) -> (B, S, D), aux, drops, counts and
    the expert FFN's rows run, summed over devices."""
    B, S, D = x.shape
    if pctx.mesh is None:
        y, aux, stats = moe_layer_local(
            x.reshape(-1, D), mp, mcfg, axis_name=None,
            router_bias=router_bias)
        return (y.reshape(B, S, D), aux,
                stats.drops_dispatch + stats.drops_slot, stats.counts,
                stats.rows_run)

    from jax.sharding import PartitionSpec as P

    ba, ma = pctx.batch_axes, pctx.ep_axes
    ep_flat = ma if isinstance(ma, tuple) else (ma,)
    if B % pctx.batch_size_divisor != 0:
        ba = ()                       # tiny batch: replicate over DP axes
    replicated = mcfg.dispatch_mode == "replicated"
    seq_ok = (not replicated) and S % pctx.ep_size == 0
    x_spec = P(ba, ma, None) if seq_ok else P(ba, None, None)

    all_axes = (*ba, *ep_flat)

    def local(x, router, w1, w3, w2, sw1, sw3, sw2, bias):
        Bl, Sl, _ = x.shape
        params = MoEParams(router, w1, w3, w2, sw1, sw3, sw2)
        y, aux, stats = moe_layer_local(
            x.reshape(-1, D), params, mcfg, axis_name=ma, router_bias=bias)
        drops = (stats.drops_dispatch + stats.drops_slot)[None]
        # Global per-expert load (replicated): drives the aux-free bias
        # update and the load-trace benchmarks.
        if replicated:
            counts = jax.lax.psum(stats.counts, ba)  # identical across model
        else:
            counts = jax.lax.psum(stats.counts, all_axes)
        return (y.reshape(Bl, Sl, D), aux[None], drops, counts,
                stats.rows_run[None])

    has_shared = mp.shared_w1 is not None
    sw_spec = P(None, None) if has_shared else P()
    bias_spec = P(None) if router_bias is not None else P()
    fn = jax.shard_map(
        local, mesh=pctx.mesh, check_vma=False,
        in_specs=(x_spec, P(None, None), P(ma, None, None),
                  P(ma, None, None), P(ma, None, None), sw_spec, sw_spec,
                  sw_spec, bias_spec),
        out_specs=(x_spec, P(all_axes), P(all_axes), P(None), P(all_axes)),
    )
    y, aux, drops, counts, rows = fn(x, mp.router, mp.w1, mp.w3, mp.w2,
                                     mp.shared_w1, mp.shared_w3, mp.shared_w2,
                                     router_bias)
    return y, aux.sum(), drops.sum(), counts, rows.sum()


def _attention(h: jax.Array, bp: BlockParams, cfg: ModelConfig,
               rcfg: RuntimeConfig, *, cache, decode: bool, valid_len):
    """The attention mixer of a block, with its cache write, under the
    named scope ``attn``.  Returns (output, new cache)."""
    acfg = attn_config(cfg)
    new_cache = cache
    with jax.named_scope("attn"):
        if decode:
            if cfg.is_mla:
                att, new_cache = attn_mod.mla_decode(h, cache, bp.attn, acfg)
            else:
                att, new_cache = attn_mod.gqa_decode(
                    h, cache, bp.attn, acfg, block_kv=rcfg.block_kv,
                    unroll=rcfg.analysis_unroll)
        elif cache is not None:  # chunked prefill writes the cache
            if cfg.is_mla:
                att, new_cache = attn_mod.mla_prefill(
                    h, cache, bp.attn, acfg, valid_len=valid_len,
                    block_kv=rcfg.block_kv, unroll=rcfg.analysis_unroll)
            else:
                att, new_cache = attn_mod.gqa_prefill(
                    h, cache, bp.attn, acfg, valid_len=valid_len,
                    block_kv=rcfg.block_kv, unroll=rcfg.analysis_unroll)
        elif cfg.is_mla:
            att = attn_mod.mla_attention(h, bp.attn, acfg,
                                         block_kv=rcfg.block_kv,
                                         unroll=rcfg.analysis_unroll)
        else:
            att = attn_mod.gqa_attention(h, bp.attn, acfg,
                                         block_kv=rcfg.block_kv,
                                         unroll=rcfg.analysis_unroll)
    return att, new_cache


def block_apply(
    x: jax.Array,
    bp: BlockParams,
    kind: str,
    cfg: ModelConfig,
    rcfg: RuntimeConfig,
    pctx: ParallelCtx,
    *,
    cache=None,
    router_bias: jax.Array | None = None,
    decode: bool = False,
    valid_len=None,
):
    """One residual block.  Returns (x, aux, drops, counts, rows_run,
    new_cache): the MoE layer's dropped pairs, per-expert load and expert
    FFN rows run (zeros without experts).

    Modes: train/full forward (cache None), chunked prefill (cache given,
    decode False -- writes the cache at offset cache.length), decode
    (cache given, decode True, S == 1).  Attention (with its cache write)
    runs under the named scope ``attn`` and a dense FFN under
    ``ffn.dense``; the MoE stages name their own (``moe.*``).
    """
    mixer, ffn_kind = kind.split("+")
    aux = jnp.zeros((), jnp.float32)
    drops = jnp.zeros((), jnp.int32)
    counts = jnp.zeros((cfg.moe.num_experts if cfg.moe else 1,), jnp.int32)
    rows_run = jnp.zeros((), jnp.int32)
    new_cache = cache

    x = wsc(x, pctx, "seq", decode=decode)
    h = rms_norm(x, bp.norm1)
    if mixer == "attn":
        # Sequence parallelism: gather S at mixer entry (heads shard over
        # the model axis inside), reduce-scatter back to seq-sharded.
        h = wsc(h, pctx, "full", decode=decode)
        att, new_cache = _attention(h, bp, cfg, rcfg, cache=cache,
                                    decode=decode, valid_len=valid_len)
        x = x + wsc(att, pctx, "seq", decode=decode)
    else:
        scfg = ssm_config(cfg)
        h = wsc(h, pctx, "full", decode=decode)
        if decode:
            y, new_cache = ssm_mod.ssd_decode(h, cache, bp.ssm, scfg)
        elif cache is not None:
            y, new_cache = ssm_mod.ssd_prefill(h, cache, bp.ssm, scfg,
                                               unroll=rcfg.analysis_unroll)
        else:
            y, _final = ssm_mod.ssd_forward(h, bp.ssm, scfg,
                                            use_kernel=rcfg.use_kernel,
                                            unroll=rcfg.analysis_unroll)
        x = x + wsc(y, pctx, "seq", decode=decode)

    if ffn_kind != "none":
        h2 = rms_norm(x, bp.norm2)
        if ffn_kind == "moe":
            B, S, _ = x.shape
            mcfg = block_moe_config(cfg, rcfg, pctx, B, S, decode=decode)
            y2, aux, drops, counts, rows_run = _ep_moe_block(
                h2, bp.moe, mcfg, pctx, router_bias)
        else:
            # Dense FFN: gather S, hidden shards over model, scatter back.
            h2 = wsc(h2, pctx, "full", decode=decode)
            with jax.named_scope("ffn.dense"):
                y2 = dense_swiglu(h2, *bp.ffn)
            y2 = wsc(y2, pctx, "seq", decode=decode)
        x = x + y2
    return x, aux, drops, counts, rows_run, new_cache


def segment_apply(
    x: jax.Array,
    seg: Segment,
    params,                     # BlockParams stacked (L, ...) or tuple of L
    cfg: ModelConfig,
    rcfg: RuntimeConfig,
    pctx: ParallelCtx,
    *,
    caches=None,                # stacked cache pytree or None
    router_bias=None,           # (L_seg, E) per-layer aux-free bias or None
    decode: bool = False,
    valid_len=None,
):
    """Run one homogeneous segment (scan if stacked, loop otherwise).

    Returns (x, aux_sum, drops (L_seg,), counts (L_seg, E), rows_run
    (L_seg,), new_caches): the routed pairs each layer dropped, its
    per-expert load and the rows its expert FFN ran.
    """
    aux_tot = jnp.zeros((), jnp.float32)

    if seg.kind == "cycle":
        # Heterogeneous repeating period: scan over cycle repetitions with
        # the period unrolled inside the body.  params/caches are tuples of
        # len(cycle) entries, each stacked over n_cycles.
        p = len(seg.cycle)
        E = cfg.moe.num_experts if cfg.moe else 1

        def body(x, layer_in):
            aux_c = jnp.zeros((), jnp.float32)
            drops_c = []
            counts_c = []
            rows_c = []
            nc_list = []
            for j, kind_j in enumerate(seg.cycle):

                def run(xx, pp, cc, bb, kind=kind_j):
                    return block_apply(xx, pp, kind, cfg, rcfg, pctx,
                                       cache=cc, router_bias=bb,
                                       decode=decode, valid_len=valid_len)

                if rcfg.remat and not decode and caches is None:
                    run = jax.checkpoint(run, prevent_cse=False)
                cache_j = (None if layer_in.get("cache") is None
                           else layer_in["cache"][j])
                bias_j = (None if layer_in.get("bias") is None
                          else layer_in["bias"][j])
                x, aux, drops, counts, rows, ncj = run(
                    x, layer_in["p"][j], cache_j, bias_j)
                aux_c += aux
                drops_c.append(drops)
                counts_c.append(counts)
                rows_c.append(rows)
                nc_list.append(ncj)
            outs = {"aux": aux_c, "drops": jnp.stack(drops_c),
                    "counts": jnp.stack(counts_c),
                    "rows": jnp.stack(rows_c)}
            if caches is not None:
                outs["cache"] = tuple(nc_list)
            return x, outs

        ins = {"p": params}
        if caches is not None:
            ins["cache"] = caches
        if router_bias is not None:
            ins["bias"] = router_bias.reshape(seg.n_cycles, p, -1)
        x, outs = jax.lax.scan(body, x, ins)
        counts = outs["counts"].reshape(seg.length, -1)
        return (x, outs["aux"].sum(), outs["drops"].reshape(seg.length),
                counts, outs["rows"].reshape(seg.length), outs.get("cache"))

    stacked = isinstance(params, BlockParams)  # stacked leaves (L, ...)
    if stacked and rcfg.scan_layers and seg.length >= rcfg.min_scan_len:

        def run_block(xx, pp, cc, bb):
            return block_apply(xx, pp, seg.kind, cfg, rcfg, pctx, cache=cc,
                               router_bias=bb, decode=decode,
                               valid_len=valid_len)

        if rcfg.remat and not decode and caches is None:
            run_block = jax.checkpoint(run_block, prevent_cse=False)

        def body(carry, layer_in):
            pp = layer_in["p"]
            if "layer" in layer_in:
                pp = pp._replace(moe=pp.moe._replace(
                    w1=params.moe.w1, w3=params.moe.w3, w2=params.moe.w2,
                    layer=layer_in["layer"]))
            xo, aux, drops, counts, rows, nc = run_block(
                carry, pp, layer_in.get("cache"), layer_in.get("bias"))
            out = {"aux": aux, "drops": drops, "counts": counts,
                   "rows": rows}
            if layer_in.get("cache") is not None:
                out["cache"] = nc
            return xo, out

        ins = {"p": params}
        moe = params.moe
        if moe is not None and pctx.mesh is None:
            # The expert stacks stay whole and each layer reads its experts
            # at its index: a scanned slice of them would be copied out to
            # feed the count-aware FFN kernel.  On a mesh the layer's
            # shard_map takes the sliced shard of its experts.
            ins["p"] = params._replace(moe=moe._replace(w1=None, w3=None,
                                                        w2=None))
            ins["layer"] = jnp.arange(seg.length, dtype=jnp.int32)
        if caches is not None:
            ins["cache"] = caches
        if router_bias is not None:
            ins["bias"] = router_bias
        x, outs = jax.lax.scan(body, x, ins)
        aux_tot += outs["aux"].sum()
        return (x, aux_tot, outs["drops"], outs["counts"], outs["rows"],
                outs.get("cache"))

    # Unstacked / short segment: python loop.
    if stacked:
        plist = [jax.tree.map(lambda a: a[i], params)
                 for i in range(seg.length)]
    else:
        plist = list(params)
    new_caches = []
    drops_l = []
    counts_l = []
    rows_l = []
    for i, bp in enumerate(plist):
        cache_l = None
        if caches is not None:
            cache_l = (caches[i] if isinstance(caches, (list, tuple))
                       else jax.tree.map(lambda a: a[i], caches))
        bias_l = None if router_bias is None else router_bias[i]

        def run_block(xx, pp, cc, bb, kind=seg.kind):
            return block_apply(xx, pp, kind, cfg, rcfg, pctx, cache=cc,
                               router_bias=bb, decode=decode,
                               valid_len=valid_len)

        if rcfg.remat and not decode and caches is None:
            run_block = jax.checkpoint(run_block, prevent_cse=False)
        x, aux, drops, counts, rows, nc = run_block(x, bp, cache_l, bias_l)
        aux_tot += aux
        drops_l.append(drops)
        counts_l.append(counts)
        rows_l.append(rows)
        new_caches.append(nc)
    drops_seg = jnp.stack(drops_l) if drops_l else jnp.zeros((0,), jnp.int32)
    counts_seg = jnp.stack(counts_l) if counts_l else jnp.zeros(
        (0, 1), jnp.int32)
    rows_seg = jnp.stack(rows_l) if rows_l else jnp.zeros((0,), jnp.int32)
    if caches is None:
        new_caches = None
    elif not isinstance(caches, (list, tuple)):
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
    return x, aux_tot, drops_seg, counts_seg, rows_seg, new_caches
