"""End-to-end training driver.

Trains any registered arch (``--reduce``d for CPU, or at published widths
cut in depth only) on the synthetic domain-mixture stream with the
fault-tolerant supervisor: optional periodic async checkpoints, crash
recovery with deterministic replay, straggler tracking.  With a mesh, the
train state is initialised and kept sharded by the rules of
:mod:`repro.parallel.sharding`, so each device materialises only its share.

Example (CPU, ~100M-class reduced MoE for a few hundred steps):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-235b-a22b \
      --reduce --steps 200 --batch 8 --seq 128 --balancer ultraep
"""

from __future__ import annotations

import argparse
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.reduce import depth_cut, reduced
from repro.core.balancer import BalancerConfig
from repro.data.pipeline import DataConfig, SyntheticLMStream
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import pctx_for_mesh
from repro.launch.specs import BIG_ARCHS, train_state_specs
from repro.models.model import init_lm, param_count
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.optim import adafactor, adamw, cosine_schedule
from repro.parallel.sharding import batch_specs, lm_param_specs
from repro.train.fault import Supervisor, SupervisorConfig
from repro.train.loop import TrainConfig, init_train_state, make_train_step

__all__ = ["Trained", "compile_step", "main", "train"]


class Trained(NamedTuple):
    losses: list[float]
    grad_norms: list[float]
    drops: list[int]
    restarts: int          # supervisor recoveries; 0 on a clean run
    params: int            # parameter count
    param_bytes: list[int]  # parameter bytes held by each device, by id
    first_step_s: float    # first step: compile and one run


def _named(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


class _Setup(NamedTuple):
    cfg: Any
    rcfg: RuntimeConfig
    key: jax.Array
    init_fn: Any            # jitted: key -> TrainState (sharded on a mesh)
    step_fn: Any            # jitted, state donated
    state_shardings: Any    # None without a mesh
    batch_fn: Any           # step -> batch


def _setup(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 128,
           balancer: str = "ultraep", reduce: bool = False, lr: float = 3e-3,
           microbatches: int = 1, cf_slot: float = 4.0, d_model: int = 64,
           num_layers: int | None = None, dtype: Any = jnp.float32,
           mesh=None, seed: int = 0) -> _Setup:
    cfg = (reduced(get_config(arch), layers=num_layers, d_model=d_model)
           if reduce else depth_cut(get_config(arch), num_layers))
    rcfg = RuntimeConfig(
        balancer=BalancerConfig(mode=balancer,
                                n_slot=cfg.moe.n_slot if cfg.moe else 2),
        cf_pair=4.0, cf_slot=cf_slot, dtype=jnp.dtype(dtype),
        # Remat frees one layer's activations while the next runs; a
        # single layer has no next, and its recompute only costs compile
        # time (a quarter of it for one qwen3-235b-a22b layer on a v5e).
        remat=cfg.num_layers > 1,
    )
    pctx = ParallelCtx(mesh=None) if mesh is None else pctx_for_mesh(mesh)
    sched = cosine_schedule(lr, warmup=max(steps // 20, 5), total=steps)
    opt = (adafactor if arch in BIG_ARCHS and not reduce else adamw)(sched)

    def init_state(key):
        return init_train_state(init_lm(key, cfg, rcfg, pctx), opt, cfg)

    key = jax.random.PRNGKey(seed)
    step = make_train_step(cfg, rcfg, pctx, opt,
                           TrainConfig(microbatches=microbatches))
    if mesh is None:
        init_fn = jax.jit(init_state)
        step_fn = jax.jit(step, donate_argnums=(0,))
        state_shardings = None
    else:
        pspecs = lm_param_specs(cfg, rcfg, pctx)
        state_shardings = _named(mesh, train_state_specs(
            pspecs, jax.eval_shape(init_state, key)))
        init_fn = jax.jit(init_state, out_shardings=state_shardings)
        step_fn = jax.jit(
            step, donate_argnums=(0,),
            in_shardings=(state_shardings, _named(mesh, batch_specs(
                cfg, pctx, "train", global_batch=batch))),
            out_shardings=(state_shardings, None))

    stream = SyntheticLMStream(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed))

    def batch_fn(step):
        b = stream.batch(step)
        if cfg.frontend == "audio_frames":
            # Stub frontend: derive frame embeddings from token ids.
            key = jax.random.PRNGKey(step)
            b = {"frames": jax.random.normal(key, (batch, seq, cfg.d_model)),
                 "targets": jnp.asarray(b["targets"])}
            return b
        out = {"tokens": jnp.asarray(b["tokens"]),
               "targets": jnp.asarray(b["targets"])}
        if cfg.frontend == "vision_patches":
            out["patches"] = jax.random.normal(
                jax.random.PRNGKey(step), (batch, cfg.num_patches,
                                           cfg.d_model))
        return out

    return _Setup(cfg, rcfg, key, init_fn, step_fn, state_shardings,
                  batch_fn)


def compile_step(arch: str, **setup_kw) -> float:
    """Compile the step that ``train(arch, **setup_kw)`` runs, without
    placing or running anything; returns the seconds it took.

    ``setup_kw`` are :func:`train`'s model, data and mesh arguments (all
    but ``ckpt_dir``, ``ckpt_every``, ``log_every`` and ``on_metrics``).
    With the persistent compile cache on, that ``train`` call then loads
    the program instead of compiling it, so a caller can compile the next
    run's step in a thread while this one trains.
    """
    s = _setup(arch, **setup_kw)
    t0 = time.perf_counter()
    s.step_fn.lower(jax.eval_shape(s.init_fn, s.key), s.batch_fn(0)).compile()
    return time.perf_counter() - t0


def train(arch: str, *, steps: int = 100, batch: int = 8, seq: int = 128,
          balancer: str = "ultraep", reduce: bool = False, lr: float = 3e-3,
          microbatches: int = 1, cf_slot: float = 4.0,
          ckpt_dir: str | None = None, ckpt_every: int = 50, d_model: int = 64,
          num_layers: int | None = None, dtype: Any = jnp.float32,
          mesh=None, log_every: int = 10, seed: int = 0,
          on_metrics=None) -> Trained:
    """Train for ``steps`` steps; ``mesh`` None runs on one device.

    ``ckpt_dir`` None trains without checkpoints (and so without recovery).
    ``cf_slot`` sizes each expert slot's capacity, as a multiple of the
    balanced load (:func:`repro.moe.layer.default_capacities`).  Archs
    whose AdamW state cannot fit at published widths train with Adafactor,
    as the dry run does.
    """
    s = _setup(arch, steps=steps, batch=batch, seq=seq, balancer=balancer,
               reduce=reduce, lr=lr, microbatches=microbatches,
               cf_slot=cf_slot, d_model=d_model, num_layers=num_layers,
               dtype=dtype, mesh=mesh, seed=seed)
    cfg, rcfg = s.cfg, s.rcfg
    state = s.init_fn(s.key)

    losses, gnorms, drops = [], [], []

    def _metrics(step, m):
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
        drops.append(int(m["drops"]))
        if on_metrics:
            on_metrics(step, m)
        if step % log_every == 0:
            print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {gnorms[-1]:.3f}  drops {drops[-1]}", flush=True)

    sup = Supervisor(
        SupervisorConfig(checkpoint_dir=ckpt_dir,
                         checkpoint_every=ckpt_every),
        s.step_fn, s.batch_fn, state_shardings=s.state_shardings)
    n_params = param_count(state.params)
    per_device: dict[int, int] = {}
    for leaf in jax.tree.leaves(state.params):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (per_device.get(shard.device.id, 0)
                                           + shard.data.nbytes)
    print(f"arch={cfg.name} layers={cfg.num_layers} params={n_params:,} "
          f"dtype={rcfg.dtype.name} balancer={balancer} "
          f"mesh={dict(mesh.shape) if mesh is not None else None}",
          flush=True)
    t0 = time.monotonic()
    state, _ = sup.run(state, 0, 1, on_metrics=_metrics)
    first_step_s = time.monotonic() - t0
    t0 = time.monotonic()
    state, final_step = sup.run(state, 1, steps - 1, on_metrics=_metrics)
    dt = time.monotonic() - t0
    print(f"done: {final_step} steps, first {first_step_s:.1f}s, then "
          f"{(steps - 1) / max(dt, 1e-9):.2f} steps/s; "
          f"final loss {losses[-1]:.4f}", flush=True)
    return Trained(losses, gnorms, drops, sup.restarts, n_params,
                   [per_device[d] for d in sorted(per_device)], first_step_s)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--balancer", default="ultraep")
    ap.add_argument("--reduce", action="store_true",
                    help="reduce every width (CPU tests and examples)")
    ap.add_argument("--num-layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=64,
                    help="width of a --reduce'd model")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)
    use_compile_cache()
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          balancer=args.balancer, reduce=args.reduce, lr=args.lr,
          microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, d_model=args.d_model,
          num_layers=args.num_layers, dtype=args.dtype)


if __name__ == "__main__":
    main()
