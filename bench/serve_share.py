"""Serving cells of a configuration whose chip holds one block of each
layer's experts: the chip's share of an expert-parallel deployment.

A mix whose ``kind`` is ``serve_share`` runs here.  The cell is served as
``bench/serve.py`` serves one, with its engine, warm-up, open-loop window,
latencies and sample, and differs in three things:

- the model is built with the held block (``bench/reference_share.py``'s
  ``block_of``): the router at its published width, the experts of the
  block, and slots sized dropless from the held experts (``held + n_slot``
  slots of a chunk's rows each);
- ``prefill_flops`` counts this chip's share of the model's work: the
  router at its whole width and, per token, the routed experts' work
  times the held share of the router's experts (top-k x held / width, the
  mean over tokens), padding and capacity excluded as in
  ``bench/flops.py``;
- ``correct`` is decided against ``bench/reference_share.py``.

A program without expert shares fails as it builds the model.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import flops, model, serve, traffic
from bench.reference_share import ShareReference, block_of, served_gaps
from bench.spans import Spans

__all__ = ["model_config", "dims", "Cell", "check", "run"]


def model_config(c: dict):
    """The program's ModelConfig, its MoE layers holding the block."""
    cfg = model.model_config(c)
    width, first, held = block_of(c)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=width, held_experts=held, first_expert=first))


def dims(c: dict) -> flops.Dims:
    """``bench/flops.py``'s shapes with the router at its whole width and
    the held share of the top-k pairs of a token."""
    d = flops.dims(c)
    width, _, held = block_of(c)
    return d._replace(experts=width, top_k=d.top_k * held / width)


class Cell(serve.Cell):
    """The program's engine for a configuration with a held block."""

    def __init__(self, config: dict, mix: dict, seed: int, spans: Spans):
        import jax

        from repro.models.transformer import ParallelCtx
        from repro.serving.adapter import make_engine_fns

        e = mix["engine"]
        self.mix, self.spans = mix, spans
        self.cfg = cfg = model_config(config)
        slots = cfg.moe.held + cfg.moe.n_slot                 # EP=1
        # Dropless as in bench/serve.py, over the held experts' slots: a
        # slot never holds more than a chunk's tokens (cap_slot = chunk).
        self.rcfg = model.runtime_config(
            config, balancer=e["balancer"], cf_pair=1.0,
            cf_slot=slots / cfg.moe.top_k)
        pctx = ParallelCtx(mesh=None)
        self.params = model.make_params(seed, cfg, self.rcfg, pctx)
        jax.block_until_ready(self.params)
        self.max_seq = (mix["prompt_len"]["max"] + mix["output_len"]["max"]
                        + e["chunk"])
        self.fns = make_engine_fns(self.params, cfg, self.rcfg, pctx,
                                   max_seq=self.max_seq)


def check(config: dict, seed: int, picked, *, control: bool = False) -> dict:
    """``bench/serve.py``'s check against the held-block reference."""
    ref = ShareReference(config, seed)
    gaps, cgaps = [], []
    for _, prompt, out in picked:
        g, cg = served_gaps(ref, prompt, out, control=control)
        gaps.append(g)
        if control:
            cgaps.append(cg)
    ref.free()
    res = {"tokens_compared": int(sum(len(o) for _, _, o in picked))}
    for name, g in (("served", gaps), ("control", cgaps)):
        if g:
            g = np.concatenate(g)
            res[f"{name}_logit_gap"] = float(g.max())
            res[f"{name}_mismatch_share"] = float(np.mean(g > 0))
    return res


def prefill_flops(d: flops.Dims, reqs, started: dict, chunk: int) -> float:
    """Operations of every chunk prefilled for the requests that started."""
    total = 0
    for r in reqs:
        if r.rid in started:
            n = len(r.prompt)
            for s in range(0, n, chunk):
                k = min(chunk, n - s)
                total += flops.span_flops(d, s, k,
                                          head_tokens=int(s + k == n))
    return total


def run(ctx) -> dict:
    import jax

    since = lambda: round(time.perf_counter() - ctx.t_start, 3)  # noqa: E731
    phases = {"start": since()}
    spans = Spans(ctx.trace)
    cell = Cell(ctx.config, ctx.traffic, ctx.seed, spans)
    phases["engine_built"] = since()
    cell.warm_up()
    phases["warmed_up"] = since()
    reqs = traffic.serve_schedule(ctx.traffic, cell.cfg.vocab_size,
                                  ctx.seed, ctx.seconds)
    setup_s = time.perf_counter() - ctx.t_start

    compiled = ctx.compiles[0]
    with ctx.tracing():
        eng, log, _ = serve.serve(cell, reqs, ctx.seconds)
    compiled = ctx.compiles[0] - compiled
    started = {}                        # request id -> first prefill start
    for rid, t in log:
        started.setdefault(rid, t)
    lat = serve.latencies(reqs, eng, ctx.seconds)
    e2e = {"ttft_p90_ms": float(np.percentile(lat["ttft"], 90)) * 1e3,
           "tpot_p90_ms": float(np.percentile(lat["tpot"], 90)) * 1e3,
           "serve_tokens_per_s": lat["tokens"] / lat["end"],
           "setup_s": setup_s}
    window = {}
    for n, s, e in spans.items:
        window[n] = round(window.get(n, 0.0) + e - s, 3)
    t_open = next(s for n, s, _ in spans.items if n == "window")
    slowest = sorted(((round(e - s, 4), round(s - t_open, 3))
                      for n, s, e in spans.items if n == "decode_call"),
                     reverse=True)[:5]
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in jax.local_devices())
    obs = {
        "kind": "serve",
        "spans": list(spans.items),
        "queue_s": [t - r.due for r in reqs if (t := started.get(r.rid))
                    is not None],
        "prefill_s": spans.durations("prefill_call"),
        "decode_s": spans.durations("decode_call"),
        "prefill_flops": prefill_flops(dims(ctx.config), reqs, started,
                                       ctx.traffic["engine"]["chunk"]),
        "peak_flops": ctx.peak["bf16_flops_per_s"],
    }
    finished = [r for r in eng.finished if not r.failed and r.output]
    picked = serve.sample(finished, ctx.seed)
    del eng, cell, log
    gc.collect()
    t_check = time.perf_counter()
    checks = check(ctx.config, ctx.seed, picked)
    return {"window_compiles": compiled, "requests_done": len(finished),
            "setup_phases": phases, "window_span_s": window,
            "slowest_decode_calls": slowest,
            "check_s": time.perf_counter() - t_check, "e2e": e2e,
            "obs": obs, "attempted": len(reqs), "failed": lat["missing"],
            "memory_peak_bytes": mem, "checks": checks}
