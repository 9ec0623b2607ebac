"""Serving driver: chunked-prefill engine over a Poisson request trace.

Examples:
  # CPU, every width reduced:
  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v3-671b \
      --reduce --requests 16 --rps 4 --chunk 64
  # Chip, published widths cut to one layer:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-235b-a22b \
      --num-layers 1 --dtype bfloat16 --requests 8 --chunk 512 \
      --prompt-len 512 2048 --max-new 16
"""

from __future__ import annotations

import argparse
import time
from typing import Any, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.configs.reduce import depth_cut, reduced
from repro.core.balancer import BalancerConfig
from repro.launch.cache import use_compile_cache
from repro.models.model import LMParams, init_lm
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.serving.adapter import make_engine_fns
from repro.serving.engine import EngineConfig, Request, ServingEngine

__all__ = ["Served", "main", "serve_trace"]

_DECODE_BATCH = 4


class Served(NamedTuple):
    engine: ServingEngine
    params: LMParams
    cfg: ModelConfig
    rcfg: RuntimeConfig
    warmup_s: float        # warm-up requests: compile and run


def _timed(fn, last: list[float]):
    """Wrap a model call so the engine's clock advances by its wall time."""
    def call(*args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        last[0] = time.perf_counter() - t0
        return out
    return call


def serve_trace(arch: str, *, requests: int = 16, rps: float = 4.0,
                chunk: int = 64, max_new: int = 8, reduce: bool = False,
                num_layers: int | None = None, dtype: Any = jnp.float32,
                balancer: str = "ultraep", seed: int = 0,
                prompt_len: tuple[int, int] = (32, 200)) -> Served:
    """Serve ``requests`` seeded prompts arriving at ``rps``.

    The engine's clock advances by the measured wall time of each model
    call, so TTFT and TPOT are device-backed latencies plus queueing.
    Warm-up requests compile the engine's path before the trace.
    """
    cfg = (reduced(get_config(arch), layers=num_layers) if reduce
           else depth_cut(get_config(arch), num_layers))
    if not cfg.has_decode:
        raise ValueError(f"{arch} is encoder-only; no serving path")
    rcfg = RuntimeConfig(
        balancer=BalancerConfig(mode=balancer,
                                n_slot=cfg.moe.n_slot if cfg.moe else 2),
        cf_pair=4.0, cf_slot=4.0, scan_layers=True, remat=False,
        dtype=jnp.dtype(dtype),
    )
    pctx = ParallelCtx(mesh=None)
    params = jax.jit(lambda key: init_lm(key, cfg, rcfg, pctx))(
        jax.random.PRNGKey(seed))
    # Room for the longest prompt (or the warm-up's two chunks), the new
    # tokens and the padding of a last chunk.
    max_seq = max(prompt_len[1], 2 * chunk) + max_new + chunk
    # SSM prefill chunks must align with the SSD chunk size.
    if cfg.ssm is not None:
        chunk = max(chunk - chunk % cfg.ssm.chunk, cfg.ssm.chunk)

    prefill_fn, decode_fn, new_cache_fn, stack, unstack = make_engine_fns(
        params, cfg, rcfg, pctx, max_seq=max_seq)
    last = [0.0]

    def engine():
        return ServingEngine(
            EngineConfig(chunk_size=chunk, decode_batch=_DECODE_BATCH,
                         max_seq=max_seq),
            prefill_fn=_timed(prefill_fn, last),
            decode_fn=_timed(decode_fn, last), new_cache_fn=new_cache_fn,
            stack_caches=stack, unstack_caches=unstack,
            clock_fn=lambda: last[0])

    # Warm-up: a full decode batch of two-chunk requests through a
    # throwaway engine, so that everything the trace would otherwise compile
    # or load is done first: the model calls, the engine's own small array
    # ops, and the prefill of a chunk whose cache came out of the previous
    # chunk (on a v5e the first such call spent 2.7 s before its device
    # work started, after one-chunk warm-ups).
    t0 = time.perf_counter()
    warm = engine()
    for i in range(_DECODE_BATCH):
        warm.submit(Request(rid=-1 - i, prompt=np.zeros(2 * chunk, np.int32),
                            max_new_tokens=2))
    warm.run()
    warmup_s = time.perf_counter() - t0

    eng = engine()
    rng = np.random.default_rng(seed)
    t = 0.0
    for i in range(requests):
        t += rng.exponential(1.0 / rps)
        L = int(rng.integers(*prompt_len))
        if cfg.ssm is not None:
            L = max(cfg.ssm.chunk, L - L % cfg.ssm.chunk)
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=L).astype(np.int32),
            max_new_tokens=max_new, arrival=t))
    done = eng.run()
    ttft, tpot = eng.ttft(), eng.tpot()
    print(f"served {len(done)} requests  warm-up {warmup_s:.1f}s  "
          f"mean TTFT {ttft.mean()*1e3:.1f}ms  "
          f"p99 TTFT {np.percentile(ttft, 99)*1e3:.1f}ms  "
          f"mean TPOT {tpot.mean()*1e3:.2f}ms  "
          f"faults {eng.fault_counters}", flush=True)
    return Served(eng, params, cfg, rcfg, warmup_s)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rps", type=float, default=4.0)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(32, 200))
    ap.add_argument("--reduce", action="store_true",
                    help="reduce every width (CPU tests and examples)")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="cut the depth only, widths as published")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--balancer", default="ultraep")
    args = ap.parse_args(argv)
    use_compile_cache()
    serve_trace(args.arch, requests=args.requests, rps=args.rps,
                chunk=args.chunk, max_new=args.max_new, reduce=args.reduce,
                num_layers=args.num_layers, dtype=args.dtype,
                balancer=args.balancer, prompt_len=tuple(args.prompt_len))


if __name__ == "__main__":
    main()
