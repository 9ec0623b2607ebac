"""Step builders + ShapeDtypeStruct input specs for every (arch x shape).

``build_cell`` assembles, for one (architecture, shape, mesh) cell, the jit
target (train_step / prefill_step / serve_step), the argument
ShapeDtypeStructs (via ``jax.eval_shape`` -- never allocating), and the
in/out shardings.  Used by the multi-pod dry-run, the roofline harness and
the integration tests.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeSpec, get_config
from repro.models.model import (
    forward,
    decode_step,
    init_caches,
    init_lm,
    init_router_bias,
)
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.optim import adafactor, adamw
from repro.parallel import sharding as shard_rules
from repro.train.loop import TrainConfig, TrainState, init_train_state, make_train_step

__all__ = ["BIG_ARCHS", "Cell", "build_cell", "shape_supported",
           "supported_shapes", "runtime_for", "train_state_specs"]

# Archs whose AdamW state cannot fit the single-pod HBM budget use Adafactor
# for the dry-run and for training at published widths (DESIGN.md S7).
BIG_ARCHS = {"qwen2-72b", "mistral-large-123b", "deepseek-v3-671b",
             "dbrx-132b", "qwen3-235b-a22b", "glm45-106b-a12b",
             "jamba-v0.1-52b", "internvl2-26b"}


class Cell(NamedTuple):
    arch: str
    shape: str
    step_fn: Callable
    arg_shapes: tuple
    in_shardings: Any
    out_shardings: Any
    donate: tuple[int, ...]
    meta: dict


def shape_supported(cfg: ModelConfig, shape: str) -> bool:
    if shape in cfg.shape_skips:
        return False
    spec = SHAPES[shape]
    if spec.kind == "decode" and not cfg.has_decode:
        return False
    return True


def supported_shapes(cfg: ModelConfig) -> list[str]:
    return [s for s in SHAPES if shape_supported(cfg, s)]


def runtime_for(cfg: ModelConfig, shape: ShapeSpec, *, balancer_mode="ultraep",
                analysis: bool = False, **overrides) -> RuntimeConfig:
    from repro.core.balancer import BalancerConfig

    block_kv = 2048 if analysis else 512
    kw = dict(
        balancer=BalancerConfig(mode=balancer_mode,
                                n_slot=cfg.moe.n_slot if cfg.moe else 2,
                                u_min=8),
        dtype=jnp.bfloat16,
        block_kv=block_kv,
        scan_layers=not analysis,
        analysis_unroll=analysis,
        remat=shape.kind == "train",
    )
    kw.update(overrides)
    return RuntimeConfig(**kw)


def train_state_specs(param_specs, state_shape: TrainState) -> TrainState:
    """PartitionSpecs of a :class:`TrainState` whose params have
    ``param_specs``; ``state_shape`` gives the optimizer-state structure."""
    return TrainState(
        params=param_specs,
        opt_state=shard_rules.opt_state_specs(param_specs,
                                              state_shape.opt_state),
        router_bias=(None if state_shape.router_bias is None
                     else P(None, None)),
        step=P(),
    )


def _batch_shapes(cfg: ModelConfig, shape: ShapeSpec, kind: str):
    B, S = shape.global_batch, shape.seq_len
    if kind == "decode":
        S = 1
    out = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if kind == "train":
        out["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.frontend == "audio_frames":
        out["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        out.pop("tokens")
        if kind == "train":
            out["targets"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.frontend == "vision_patches" and kind != "decode":
        out["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, cfg.d_model), jnp.bfloat16)
    return out


def build_cell(
    arch: str,
    shape_name: str,
    pctx: ParallelCtx,
    *,
    balancer_mode: str = "ultraep",
    analysis: bool = False,
    num_layers_override: int | None = None,
    microbatches: int = 1,
    rcfg_overrides: dict | None = None,
) -> Cell:
    """Assemble one (arch x shape) dry-run cell."""
    cfg = get_config(arch)
    if num_layers_override is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers_override)
    shape = SHAPES[shape_name]
    if not shape_supported(get_config(arch), shape_name):
        raise ValueError(f"{arch} skips {shape_name}")
    rcfg = runtime_for(cfg, shape, balancer_mode=balancer_mode,
                       analysis=analysis, **(rcfg_overrides or {}))

    params_shape = jax.eval_shape(
        lambda: init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx))
    pspecs = shard_rules.lm_param_specs(cfg, rcfg, pctx)
    bshapes = _batch_shapes(cfg, shape, shape.kind)
    bspecs = shard_rules.batch_specs(cfg, pctx, shape.kind,
                                 global_batch=shape.global_batch)
    meta = {"cfg": cfg, "rcfg": rcfg, "shape": shape}

    if shape.kind == "train":
        opt = (adafactor(1e-4) if arch in BIG_ARCHS else adamw(3e-4))
        state_shape = jax.eval_shape(
            lambda: init_train_state(params_shape, opt, cfg))
        sspecs = train_state_specs(pspecs, state_shape)
        step = make_train_step(cfg, rcfg, pctx, opt,
                               TrainConfig(microbatches=microbatches))
        return Cell(arch, shape_name, step, (state_shape, bshapes),
                    (sspecs, bspecs), None, (0,), meta)

    if shape.kind == "prefill":
        bias = init_router_bias(cfg)

        def prefill_step(params, batch):
            logits, aux, drops, counts = forward(params, batch, cfg, rcfg,
                                                 pctx, router_bias=bias)
            return logits, drops, counts

        return Cell(arch, shape_name, prefill_step, (params_shape, bshapes),
                    (pspecs, bspecs), None, (), meta)

    # decode
    bias = init_router_bias(cfg)
    caches_shape = jax.eval_shape(
        lambda: init_caches(cfg, shape.global_batch, shape.seq_len, rcfg))
    cspecs = shard_rules.cache_specs(cfg, rcfg, pctx, shape.global_batch)

    def serve_step(params, caches, batch):
        return decode_step(params, caches, batch["tokens"], cfg, rcfg, pctx,
                           router_bias=bias)

    return Cell(arch, shape_name, serve_step,
                (params_shape, caches_shape, bshapes),
                (pspecs, cspecs, bspecs), None, (1,), meta)
