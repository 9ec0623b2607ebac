"""LM assembly: embeddings, frontend stubs, segments, losses, decode step."""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, layer_kinds
from repro.models.layers import embed, rms_norm, unembed
from repro.models.transformer import (
    BlockParams,
    ParallelCtx,
    RuntimeConfig,
    Segment,
    build_segments,
    init_block,
    init_cache_block,
    segment_apply,
    segments_for,
)

__all__ = ["LMParams", "MoECounters", "init_lm", "init_router_bias",
           "forward", "lm_loss", "blocked_lm_loss", "init_caches",
           "prefill_step", "decode_step", "param_count"]


class LMParams(NamedTuple):
    embedding: jax.Array                  # (V, D)
    frontend_proj: jax.Array | None       # (D_front, D) modality adapter stub
    segments: tuple                       # stacked BlockParams per segment
    final_norm: jax.Array                 # (D,)
    lm_head: jax.Array | None             # (V, D); None = tied


class MoECounters(NamedTuple):
    """Routed (token, expert) pairs of one step, per layer ((num_layers,)
    int32 each; zero on layers without experts).  ``held + drops +
    absent`` is the top-k pairs of every token the step routed (a prefill
    chunk's right-padding is routed too)."""

    held: jax.Array      # pairs the layer's expert slots held
    drops: jax.Array     # pairs dropped at pair or slot capacity
    absent: jax.Array    # pairs routed to experts held on other chips
                         #   (an expert share; a constant 0 otherwise)
    rows_run: jax.Array  # slot rows whose products the expert FFN
                         #   computed, over the layer's devices, the
                         #   count-aware kernel's tile padding included


def _stack_blocks(blocks: list[BlockParams]) -> BlockParams:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def init_lm(key: jax.Array, cfg: ModelConfig, rcfg: RuntimeConfig,
            pctx: ParallelCtx) -> LMParams:
    segs = segments_for(cfg, rcfg)
    keys = jax.random.split(key, cfg.num_layers + 3)
    seg_params = []
    li = 0
    for seg in segs:
        if seg.kind == "cycle":
            p = len(seg.cycle)
            blocks = [init_block(keys[li + j], cfg, seg.cycle[j % p], rcfg,
                                 pctx) for j in range(seg.length)]
            li += seg.length
            seg_params.append(tuple(
                _stack_blocks([blocks[c * p + j]
                               for c in range(seg.n_cycles)])
                for j in range(p)))
            continue
        blocks = [init_block(keys[li + j], cfg, seg.kind, rcfg, pctx)
                  for j in range(seg.length)]
        li += seg.length
        if rcfg.scan_layers and seg.length >= rcfg.min_scan_len:
            seg_params.append(_stack_blocks(blocks))
        else:
            seg_params.append(tuple(blocks))
    dtype = rcfg.dtype
    D, V = cfg.d_model, cfg.vocab_size
    frontend = None
    if cfg.frontend != "none":
        frontend = jax.random.normal(keys[-3], (D, D), dtype) * D ** -0.5
    return LMParams(
        embedding=jax.random.normal(keys[-1], (V, D), dtype) * 0.02,
        frontend_proj=frontend,
        segments=tuple(seg_params),
        final_norm=jnp.ones((D,), dtype),
        lm_head=(None if cfg.tie_embeddings
                 else jax.random.normal(keys[-2], (V, D), dtype) * 0.02),
    )


def init_router_bias(cfg: ModelConfig) -> jax.Array | None:
    """(num_layers, E) aux-free routing bias (zeros for non-MoE layers)."""
    if cfg.moe is None or not cfg.moe.use_bias:
        return None
    return jnp.zeros((cfg.num_layers, cfg.moe.num_experts), jnp.float32)


def _input_embeddings(params: LMParams, batch: dict, cfg: ModelConfig):
    """Embed tokens / splice in stub modality embeddings."""
    if cfg.frontend == "audio_frames":
        # Precomputed frame embeddings (B, S, D) through the adapter stub.
        return batch["frames"] @ params.frontend_proj
    x = embed(batch["tokens"], params.embedding)
    if cfg.frontend == "vision_patches":
        patches = batch["patches"] @ params.frontend_proj  # (B, P, D)
        P_len = patches.shape[1]
        x = jnp.concatenate([patches.astype(x.dtype), x[:, P_len:]], axis=1)
    return x


def forward(
    params: LMParams,
    batch: dict,
    cfg: ModelConfig,
    rcfg: RuntimeConfig,
    pctx: ParallelCtx,
    *,
    router_bias: jax.Array | None = None,
    return_hidden: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Full-sequence forward.

    Returns (logits, aux_loss, drops, counts) where counts is the
    (num_layers, E) realized per-layer expert load (zeros on non-MoE layers)
    -- the exact-load trace feeding the aux-free bias update and the load
    benchmarks.  ``return_hidden=True`` skips the unembedding and returns
    the final-norm hidden states instead of logits (blocked-loss path).
    """
    from repro.models.transformer import wsc

    with jax.named_scope("embed"):
        x = _input_embeddings(params, batch, cfg)
    x = wsc(x, pctx, "seq")
    segs = segments_for(cfg, rcfg)
    aux_tot = jnp.zeros((), jnp.float32)
    drops_tot = jnp.zeros((), jnp.int32)
    E = cfg.moe.num_experts if cfg.moe is not None else 1
    counts_all = jnp.zeros((cfg.num_layers, E), jnp.int32)
    for seg, sp in zip(segs, params.segments):
        bias_seg = None
        if router_bias is not None:
            bias_seg = router_bias[jnp.array(seg.layer_ids)]
        x, aux, drops, counts, _, _ = segment_apply(
            x, seg, sp, cfg, rcfg, pctx, router_bias=bias_seg)
        aux_tot += aux
        drops_tot += drops.sum()
        counts_all = jax.lax.dynamic_update_slice_in_dim(
            counts_all, counts.astype(jnp.int32), seg.layer_ids[0], axis=0)
    if return_hidden:
        with jax.named_scope("head"):
            x = rms_norm(x, params.final_norm)
        return x, aux_tot, drops_tot, counts_all
    # Seq-sharded fp32 logits: softmax/CE are then token-local (no vocab
    # collective in the loss).
    logits = wsc(_head(params, x), pctx, "seq")
    return logits, aux_tot, drops_tot, counts_all


def _head(params: LMParams, x: jax.Array) -> jax.Array:
    """Final norm and unembedding, under the named scope ``head``."""
    with jax.named_scope("head"):
        x = rms_norm(x, params.final_norm)
        head = params.embedding if params.lm_head is None else params.lm_head
        return unembed(x, head)


def lm_loss(logits: jax.Array, targets: jax.Array,
            *, z_loss: float = 1e-4) -> jax.Array:
    """Token cross-entropy (fp32) with z-loss regularisation."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - ll).mean()
    return nll + z_loss * (lse ** 2).mean()


def blocked_lm_loss(x: jax.Array, head: jax.Array, targets: jax.Array,
                    *, z_loss: float = 1e-4, chunks: int = 8,
                    unroll: bool = False) -> jax.Array:
    """Cross-entropy over sequence chunks without materialising the full
    (B, S, V) fp32 logits -- the memory-term eliminator for large-vocab
    archs (EXPERIMENTS.md SPerf iteration 2).  The chunk logits are
    recomputed in backward via jax.checkpoint.
    """
    B, S, D = x.shape
    chunks = max(1, min(chunks, S))
    while S % chunks:
        chunks -= 1
    xs = jnp.moveaxis(x.reshape(B, chunks, S // chunks, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, chunks, S // chunks), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        xc, tc = inp
        logits = jnp.einsum("bsd,vd->bsv", xc.astype(jnp.float32),
                            head.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return (carry[0] + (lse - ll).sum(), carry[1] + (lse ** 2).sum()), None

    if unroll:
        carry = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        for c in range(chunks):
            carry, _ = body(carry, (xs[c], ts[c]))
        nll, z = carry
    else:
        (nll, z), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xs, ts))
    n = B * S
    return nll / n + z_loss * z / n


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, rcfg: RuntimeConfig):
    """Per-segment decode caches (stacked to mirror the parameter layout)."""
    segs = segments_for(cfg, rcfg)
    caches = []
    for seg in segs:
        if seg.kind == "cycle":
            p = len(seg.cycle)
            caches.append(tuple(
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[init_cache_block(cfg, seg.cycle[j], batch,
                                                max_seq, rcfg.dtype)
                               for _ in range(seg.n_cycles)])
                for j in range(p)))
            continue
        entries = [init_cache_block(cfg, seg.kind, batch, max_seq, rcfg.dtype)
                   for _ in range(seg.length)]
        if rcfg.scan_layers and seg.length >= rcfg.min_scan_len:
            caches.append(jax.tree.map(lambda *xs: jnp.stack(xs), *entries))
        else:
            caches.append(tuple(entries))
    return tuple(caches)


def _cached_step(params: LMParams, caches, tokens: jax.Array,
                 cfg: ModelConfig, rcfg: RuntimeConfig, pctx: ParallelCtx, *,
                 decode: bool, valid_len, router_bias):
    """(logits, new_caches, MoECounters) of a step that reads and writes
    the caches."""
    with jax.named_scope("embed"):
        x = embed(tokens, params.embedding)
    segs = segments_for(cfg, rcfg)
    share = cfg.moe is not None and cfg.moe.holds_share
    new_caches, held, drops_all, absent, rows_all = [], [], [], [], []
    for seg, sp, cache in zip(segs, params.segments, caches):
        bias_seg = None
        if router_bias is not None:
            bias_seg = router_bias[jnp.array(seg.layer_ids)]
        x, _aux, drops, counts, rows, nc = segment_apply(
            x, seg, sp, cfg, rcfg, pctx, caches=cache,
            router_bias=bias_seg, decode=decode, valid_len=valid_len)
        new_caches.append(nc)
        routed = counts.sum(axis=-1, dtype=jnp.int32)
        if share:
            # counts span the router's width; the layer holds one block.
            lo = cfg.moe.first_expert
            here = counts[:, lo:lo + cfg.moe.held].sum(axis=-1,
                                                       dtype=jnp.int32)
            absent.append(routed - here)
            routed = here
        held.append(routed - drops)
        drops_all.append(drops)
        rows_all.append(rows)
    counters = MoECounters(
        held=jnp.concatenate(held), drops=jnp.concatenate(drops_all),
        absent=(jnp.concatenate(absent) if share
                else jnp.zeros((cfg.num_layers,), jnp.int32)),
        rows_run=jnp.concatenate(rows_all))
    return _head(params, x), tuple(new_caches), counters


def prefill_step(
    params: LMParams,
    caches,
    tokens: jax.Array,
    cfg: ModelConfig,
    rcfg: RuntimeConfig,
    pctx: ParallelCtx,
    *,
    valid_len=None,
    router_bias: jax.Array | None = None,
):
    """Chunked prefill: run a (B, C) chunk, writing caches at their offset.

    Returns (logits, new_caches, MoECounters).  The chunk's absolute
    position comes from the caches' ``length`` counters.
    """
    return _cached_step(params, caches, tokens, cfg, rcfg, pctx,
                        decode=False, valid_len=valid_len,
                        router_bias=router_bias)


def decode_step(
    params: LMParams,
    caches,
    tokens: jax.Array,
    cfg: ModelConfig,
    rcfg: RuntimeConfig,
    pctx: ParallelCtx,
    *,
    router_bias: jax.Array | None = None,
):
    """One-token decode.  tokens: (B, 1).  Returns (logits, new_caches,
    MoECounters)."""
    return _cached_step(params, caches, tokens, cfg, rcfg, pctx,
                        decode=True, valid_len=None, router_bias=router_bias)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
