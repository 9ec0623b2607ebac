"""Glue: bind a model config to the ServingEngine callbacks."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ModelConfig, layer_kinds
from repro.models.model import LMParams, decode_step, init_caches, prefill_step
from repro.models.transformer import ParallelCtx, RuntimeConfig, moe_slot_rows

__all__ = ["make_engine_fns"]


def make_engine_fns(params: LMParams, cfg: ModelConfig, rcfg: RuntimeConfig,
                    pctx: ParallelCtx, *, max_seq: int):
    """Returns (prefill_fn, decode_fn, new_cache_fn, stack_caches,
    unstack_caches).

    ``params`` enter the jitted steps as arguments: closed over, they would
    be baked into each program as constants.  The steps' MoE counters go
    to ``repro.tracing.count`` while a trace records, with the top-k pairs
    of the valid tokens (a prefill chunk less its right-padding), and are
    dropped otherwise.
    """

    @jax.jit
    def _prefill(params, tokens, caches, valid_len):
        return prefill_step(params, caches, tokens, cfg, rcfg, pctx,
                            valid_len=valid_len)

    @jax.jit
    def _decode(params, tokens, caches):
        return decode_step(params, caches, tokens, cfg, rcfg, pctx)

    moe_layers = tuple(kind.endswith("+moe") for kind in layer_kinds(cfg))

    @functools.cache
    def slot_rows(batch, seq, decode):
        """Capacity rows of each layer's expert slots per call (0: no
        experts)."""
        return tuple(moe_slot_rows(cfg, rcfg, pctx, batch, seq, decode=decode)
                     if moe else 0 for moe in moe_layers)

    def valid_pairs(tokens):
        """Top-k pairs of ``tokens`` valid tokens, per layer."""
        return tuple(tokens * cfg.moe.top_k if moe else 0
                     for moe in moe_layers)

    def prefill_fn(tokens, caches, start, valid_len):
        logits, caches, counters = _prefill(
            params, tokens, caches, jnp.asarray(valid_len, jnp.int32))
        if tracing.active():
            batch, seq = tokens.shape
            tracing.count("prefill", counters, slot_rows(batch, seq, False),
                          valid_pairs(batch * int(valid_len)))
        return logits, caches

    def decode_fn(tokens, caches):
        logits, caches, counters = _decode(params, tokens, caches)
        if tracing.active():
            # A short decode batch repeats a real row, which the counters
            # cannot tell from it: every row counts as valid.
            tracing.count("decode", counters, slot_rows(*tokens.shape, True),
                          valid_pairs(tokens.size))
        return logits, caches

    def new_cache_fn(batch):
        return init_caches(cfg, batch, max_seq, rcfg)

    # Structure-aware batch concat: stacked segments carry a leading layer
    # axis, so their batch dim is axis 1; unstacked entries use axis 0.
    from repro.models.transformer import segments_for

    segs = segments_for(cfg, rcfg)
    stacked_flags = [s.kind == "cycle"
                     or (rcfg.scan_layers and s.length >= rcfg.min_scan_len)
                     for s in segs]

    def stack_caches(caches_list):
        out = []
        for i, stacked in enumerate(stacked_flags):
            ax = 1 if stacked else 0
            seg_caches = [c[i] for c in caches_list]
            out.append(jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=ax), *seg_caches))
        return tuple(out)

    def unstack_caches(caches, n):
        outs = []
        for b in range(n):
            per = []
            for i, stacked in enumerate(stacked_flags):
                ax = 1 if stacked else 0
                per.append(jax.tree.map(
                    lambda a, b=b, ax=ax: jax.lax.slice_in_dim(a, b, b + 1,
                                                               axis=ax),
                    caches[i]))
            outs.append(tuple(per))
        return outs

    return (prefill_fn, decode_fn, new_cache_fn, stack_caches,
            unstack_caches)
