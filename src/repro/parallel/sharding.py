"""PartitionSpec builders for every parameter / activation / cache pytree.

Sharding policy (DESIGN.md S5), MaxText-style single model axis:

  * ``model`` axis: TP for attention heads & FFN hidden; EP for experts;
    vocab for embedding/logits; sequence for long activations and KV caches.
  * ``data`` (+ ``pod``) axes: batch DP and FSDP -- every large parameter is
    additionally sharded over the DP axes on a divisible dimension, so
    optimizer state (same specs) is ZeRO-sharded for free.
  * Small vectors (norms, biases, (H,) ssm params) are replicated.

Specs are built *by construction*, mirroring ``init_lm`` exactly -- no
string-path matching.  Every helper degrades to replication when a dimension
is not divisible by the axis size (e.g. mamba2's 24 heads on a 16-way axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.topology import Topology
from repro.models.attention import GQAParams, KVCache, MLAParams
from repro.models.model import LMParams
from repro.models.ssm import SSMParams, SSMState
from repro.models.transformer import (
    BlockParams,
    ParallelCtx,
    RuntimeConfig,
    build_segments,
    segments_for,
)
from repro.moe.layer import MoEParams

__all__ = ["MeshAxes", "Topology", "lm_param_specs", "batch_specs",
           "cache_specs", "opt_state_specs", "activation_spec", "from_ctx",
           "topology_from_ctx"]


def topology_from_ctx(pctx: ParallelCtx, **link_kw) -> Topology:
    """Derive the EP :class:`Topology` of a mesh context.

    A flat mesh is a single rack of ``ep_size`` ranks; a factored mesh
    (``pctx.rack_axis`` set) is ``racks x lanes``.  ``link_kw`` overrides the
    per-tier alpha/beta link model for the comm planner / benchmarks.
    """
    if pctx.rack_axis is None:
        return Topology.flat(pctx.ep_size, **link_kw)
    return Topology(racks=pctx.racks,
                    ranks_per_rack=pctx.ep_size // pctx.racks, **link_kw)


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Axis names + sizes of the active mesh.

    ``model`` is a single axis name on a flat mesh, or the factored
    ``(rack, lane)`` axis tuple of a two-level EP topology -- every spec
    helper shards the model dimension over the *product* either way
    (PartitionSpec entries accept axis tuples), so TP/EP/vocab/sequence
    sharding is topology-transparent.
    """

    batch: tuple[str, ...]        # e.g. ("pod", "data") or ("data",)
    model: str | tuple[str, ...]  # "model" | ("rack", "model")
    sizes: dict[str, int]

    @property
    def batch_size(self) -> int:
        return int(np.prod([self.sizes[a] for a in self.batch]))

    @property
    def model_size(self) -> int:
        m = (self.model,) if isinstance(self.model, str) else self.model
        return int(np.prod([self.sizes[a] for a in m]))

    def div(self, n: int, axes) -> bool:
        if isinstance(axes, str):
            axes = (axes,)
        return n % int(np.prod([self.sizes[a] for a in axes])) == 0


def from_ctx(pctx: ParallelCtx) -> MeshAxes:
    sizes = ({a: int(s) for a, s in pctx.mesh.shape.items()}
             if pctx.mesh is not None else {})
    return MeshAxes(batch=pctx.batch_axes, model=pctx.ep_axes, sizes=sizes)


def _mm(ax: MeshAxes, n: int):
    """'model' if divisible else None."""
    return ax.model if ax.sizes and ax.div(n, ax.model) else None


def _dd(ax: MeshAxes, n: int):
    """batch axes (FSDP) if divisible else None."""
    return ax.batch if ax.sizes and ax.div(n, ax.batch) else None


def _gqa_specs(cfg: ModelConfig, ax: MeshAxes, stacked: bool) -> GQAParams:
    L = (None,) if stacked else ()
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    m_q = _mm(ax, H * hd)
    m_kv = _mm(ax, Hkv * hd)
    d_fs = _dd(ax, cfg.d_model)
    return GQAParams(
        wq=P(*L, d_fs, m_q),
        wk=P(*L, d_fs, m_kv),
        wv=P(*L, d_fs, m_kv),
        wo=P(*L, m_q, d_fs),
        bq=P(*L, m_q) if cfg.qkv_bias else None,
        bk=P(*L, m_kv) if cfg.qkv_bias else None,
        bv=P(*L, m_kv) if cfg.qkv_bias else None,
        q_norm=P(*L, None) if cfg.qk_norm else None,
        k_norm=P(*L, None) if cfg.qk_norm else None,
    )


def _mla_specs(cfg: ModelConfig, ax: MeshAxes, stacked: bool) -> MLAParams:
    L = (None,) if stacked else ()
    H = cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    d_fs = _dd(ax, cfg.d_model)
    return MLAParams(
        wq_a=P(*L, d_fs, _mm(ax, cfg.q_lora_rank)),
        q_a_norm=P(*L, None),
        wq_b=P(*L, _dd(ax, cfg.q_lora_rank), _mm(ax, H * qk)),
        wkv_a=P(*L, d_fs, None),
        kv_a_norm=P(*L, None),
        wkv_b=P(*L, _dd(ax, cfg.kv_lora_rank),
                _mm(ax, H * (cfg.qk_nope_dim + cfg.v_head_dim))),
        wo=P(*L, _mm(ax, H * cfg.v_head_dim), d_fs),
    )


def _ssm_specs(cfg: ModelConfig, ax: MeshAxes, stacked: bool) -> SSMParams:
    L = (None,) if stacked else ()
    s = cfg.ssm
    cc = s.d_inner + 2 * s.n_groups * s.d_state
    proj_out = 2 * s.d_inner + 2 * s.n_groups * s.d_state + s.d_inner // s.headdim
    return SSMParams(
        in_proj=P(*L, _dd(ax, cfg.d_model), _mm(ax, proj_out)),
        conv_w=P(*L, None, _mm(ax, cc)),
        conv_b=P(*L, _mm(ax, cc)),
        a_log=P(*L, None),
        d_skip=P(*L, None),
        dt_bias=P(*L, None),
        norm=P(*L, None),
        out_proj=P(*L, _mm(ax, s.d_inner), _dd(ax, cfg.d_model)),
    )


def _moe_specs(cfg: ModelConfig, ax: MeshAxes, stacked: bool) -> MoEParams:
    L = (None,) if stacked else ()
    m = cfg.moe
    d_fs = _dd(ax, cfg.d_model)
    f_fs = _dd(ax, m.d_ff)
    has_shared = m.n_shared_experts > 0
    fs = m.shared_d_ff * m.n_shared_experts if has_shared else 0
    return MoEParams(
        router=P(*L, None, None),
        w1=P(*L, _mm(ax, m.held), d_fs, None),
        w3=P(*L, _mm(ax, m.held), d_fs, None),
        w2=P(*L, _mm(ax, m.held), f_fs, None),
        shared_w1=P(*L, d_fs, _mm(ax, fs)) if has_shared else None,
        shared_w3=P(*L, d_fs, _mm(ax, fs)) if has_shared else None,
        shared_w2=P(*L, _mm(ax, fs), d_fs) if has_shared else None,
    )


def _block_specs(cfg: ModelConfig, kind: str, ax: MeshAxes,
                 stacked: bool) -> BlockParams:
    L = (None,) if stacked else ()
    mixer, ffn_kind = kind.split("+")
    attn = ssm = ffn = moe = None
    if mixer == "attn":
        attn = (_mla_specs(cfg, ax, stacked) if cfg.is_mla
                else _gqa_specs(cfg, ax, stacked))
    else:
        ssm = _ssm_specs(cfg, ax, stacked)
    if ffn_kind == "dense":
        d_fs = _dd(ax, cfg.d_model)
        m_f = _mm(ax, cfg.d_ff)
        ffn = (P(*L, d_fs, m_f), P(*L, d_fs, m_f), P(*L, m_f, d_fs))
    elif ffn_kind == "moe":
        moe = _moe_specs(cfg, ax, stacked)
    return BlockParams(
        norm1=P(*L, None),
        norm2=None if ffn_kind == "none" else P(*L, None),
        attn=attn, ssm=ssm, ffn=ffn, moe=moe,
    )


def lm_param_specs(cfg: ModelConfig, rcfg: RuntimeConfig,
                   pctx: ParallelCtx) -> LMParams:
    ax = from_ctx(pctx)
    segs = segments_for(cfg, rcfg)
    seg_specs = []
    for seg in segs:
        if seg.kind == "cycle":
            seg_specs.append(tuple(_block_specs(cfg, k, ax, True)
                                   for k in seg.cycle))
            continue
        stacked = rcfg.scan_layers and seg.length >= rcfg.min_scan_len
        bs = _block_specs(cfg, seg.kind, ax, stacked)
        seg_specs.append(bs if stacked else tuple(bs for _ in range(seg.length)))
    emb = P(_mm(ax, cfg.vocab_size), _dd(ax, cfg.d_model))
    return LMParams(
        embedding=emb,
        frontend_proj=(P(_dd(ax, cfg.d_model), _mm(ax, cfg.d_model))
                       if cfg.frontend != "none" else None),
        segments=tuple(seg_specs),
        final_norm=P(None),
        lm_head=None if cfg.tie_embeddings else emb,
    )


def batch_specs(cfg: ModelConfig, pctx: ParallelCtx, kind: str,
                global_batch: int | None = None):
    """Input batch PartitionSpecs.  kind: train | prefill | decode.

    Batch stays replicated when ``global_batch`` does not divide the DP
    axes (long_500k has batch=1: the data axis then parallelises nothing
    at the input; the KV cache still seq-shards over the model axis).
    """
    ax = from_ctx(pctx)
    b = ax.batch if ax.sizes else None
    if b is not None and global_batch is not None and \
            not ax.div(global_batch, ax.batch):
        b = None
    seq = ax.model if (kind != "decode" and ax.sizes) else None
    spec = {"tokens": P(b, seq)}
    if kind == "train":
        spec["targets"] = P(b, seq)
    if cfg.frontend == "audio_frames":
        spec["frames"] = P(b, seq, None)
        spec.pop("tokens")
    if cfg.frontend == "vision_patches" and kind != "decode":
        spec["patches"] = P(b, None, None)
    return spec


def _cache_entry_spec(cfg: ModelConfig, kind: str, ax: MeshAxes,
                      stacked: bool, batch: int):
    L = (None,) if stacked else ()
    mixer, _ = kind.split("+")
    b = ax.batch if ax.sizes and ax.div(batch, ax.batch) else None
    if mixer == "attn":
        # Sequence-sharded cache over the model axis (flash-decode).
        if cfg.is_mla:
            return KVCache(k=P(*L, b, ax.model if ax.sizes else None, None),
                           v=P(*L, b, ax.model if ax.sizes else None, None),
                           length=P(*L, b))
        return KVCache(
            k=P(*L, b, ax.model if ax.sizes else None, None, None),
            v=P(*L, b, ax.model if ax.sizes else None, None, None),
            length=P(*L, b),
        )
    s = cfg.ssm
    cc = s.d_inner + 2 * s.n_groups * s.d_state
    return SSMState(
        s=P(*L, b, _mm(ax, s.d_inner // s.headdim), None, None),
        conv=P(*L, b, None, _mm(ax, cc)),
        length=P(*L, b),
    )


def cache_specs(cfg: ModelConfig, rcfg: RuntimeConfig, pctx: ParallelCtx,
                batch: int):
    ax = from_ctx(pctx)
    segs = segments_for(cfg, rcfg)
    out = []
    for seg in segs:
        if seg.kind == "cycle":
            out.append(tuple(_cache_entry_spec(cfg, k, ax, True, batch)
                             for k in seg.cycle))
            continue
        stacked = rcfg.scan_layers and seg.length >= rcfg.min_scan_len
        es = _cache_entry_spec(cfg, seg.kind, ax, stacked, batch)
        out.append(es if stacked else tuple(es for _ in range(seg.length)))
    return tuple(out)


def opt_state_specs(param_specs, opt_state):
    """Optimizer-state specs: AdamW m/v mirror the param specs exactly
    (ZeRO falls out of the FSDP param sharding); Adafactor's factored
    moments drop the reduced dimension's spec entry."""
    from repro.optim.optimizer import AdafactorState, AdamWState

    if isinstance(opt_state, AdamWState):
        return AdamWState(mu=param_specs, nu=param_specs)
    if isinstance(opt_state, AdafactorState):
        def row_spec(sp):
            if sp is None:
                return None
            t = tuple(sp)
            return P(*t[:-1]) if len(t) >= 2 else sp

        def col_spec(sp):
            if sp is None:
                return None
            t = tuple(sp)
            return P(*t[:-2], t[-1]) if len(t) >= 2 else P()

        is_spec = lambda x: isinstance(x, P)
        return AdafactorState(
            v_row=jax.tree.map(row_spec, param_specs, is_leaf=is_spec),
            v_col=jax.tree.map(col_spec, param_specs, is_leaf=is_spec),
        )
    raise TypeError(f"unknown optimizer state {type(opt_state)}")


def activation_spec(pctx: ParallelCtx, kind: str) -> P:
    """Residual-stream constraint: (B, S, D) batch x seq sharding."""
    ax = from_ctx(pctx)
    if not ax.sizes:
        return P()
    return P(ax.batch, ax.model if kind != "decode" else None, None)
