"""Serving cells: the program's engine, open loop, on the wall clock.

Set-up draws the weights, builds the engine's jitted calls through
``repro.serving.adapter.make_engine_fns`` and sends a full decode batch of
two-chunk requests through a throwaway engine, so that every program the
window runs is compiled or loaded first.  The window then submits each
request when it falls due and steps ``ServingEngine.run`` one iteration
at a time.  The engine's clock is the wall clock.  A request's prefill
chunks go to the device back to back, as the engine sends them; the
benchmark waits for the device only where a time is stamped: after a
request's last chunk (its first token) and after each decode call, which
the engine reads back at once.  With tracing on, every prefill call is
also waited for, so that its span holds its device time.  The window
closes ``seconds`` after it opens; requests in flight then drain for up
to ``DRAIN_S`` more.

Once the window has closed, the memory peak is read and the program's
state is freed, a sample of finished requests drawn from the seed, with
the longest prompt among them, goes through the float32 reference.  For
each served token, the gap is how far its logit lies below the
reference's best logit there.  The number compared is the share of
served tokens with a gap above 0, which the reference does not put
first; the widest gap is reported beside it.  The widest gap cannot be
compared: one token whose top-k choice of experts flips on the
rounding of the router's input reads a gap of the reference's own top-2
margin, up to about 0.2 at these scales, whatever the precision.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import flops, model, traffic
from bench.reference import Reference, served_gaps
from bench.spans import Spans

__all__ = ["run", "serve", "sample", "DRAIN_S", "SAMPLE_TOKENS"]

DRAIN_S = 60.0
SAMPLE_TOKENS = 200      # served tokens the check compares, at least


class Cell:
    """The program's engine for one configuration, mix and seed."""

    def __init__(self, config: dict, mix: dict, seed: int, spans: Spans):
        import jax

        from repro.models.transformer import ParallelCtx
        from repro.serving.adapter import make_engine_fns

        e = mix["engine"]
        self.mix, self.spans = mix, spans
        self.cfg = cfg = model.model_config(config)
        slots = cfg.moe.num_experts + cfg.moe.n_slot          # EP=1
        # No routed (token, expert) pair can drop.  A token picks an expert
        # once, so a slot never holds more than a chunk's tokens, and the
        # one pair buffer never more than its items: cap_slot =
        # ceil(chunk * k * cf_slot / slots) = chunk, cap_pair = chunk * k.
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

    def engine(self, clock, log=None):
        """A ServingEngine on ``clock``; ``log`` collects (request id,
        start) of each request's prefill."""
        import jax

        from repro.serving.engine import EngineConfig, ServingEngine

        prefill, decode, new_cache, stack, unstack = self.fns
        sp, e = self.spans, self.mix["engine"]
        eng = ServingEngine(
            EngineConfig(chunk_size=e["chunk"], decode_batch=e["decode_batch"],
                         max_seq=self.max_seq),
            prefill_fn=sp.wrap("prefill_call", prefill, sync=sp.trace),
            decode_fn=sp.wrap("decode_call", decode, sync=True),
            new_cache_fn=sp.wrap("new_cache", new_cache),
            stack_caches=sp.wrap("stack_caches", stack),
            unstack_caches=sp.wrap("unstack_caches", unstack),
            clock_fn=lambda: clock() - eng.now)
        prefill_req = eng.prefill

        def stamped_prefill(req):
            if log is not None:
                log.append((req.rid, clock()))
            last, cache = prefill_req(req)
            # The engine stamps the first token with ``eng.now``.
            jax.block_until_ready(last)
            eng.now = clock()
            return last, cache

        eng.prefill = stamped_prefill
        return eng

    def warm_up(self):
        """A full decode batch of two-chunk requests: every program and
        host op the window runs, compiled or loaded."""
        from repro.serving.engine import Request

        e = self.mix["engine"]
        eng = self.engine(self.spans.now)
        for i in range(e["decode_batch"]):
            eng.submit(Request(rid=-1 - i, prompt=np.full(
                2 * e["chunk"] - 1 - i, 7, np.int32), max_new_tokens=2))
        eng.run()
        self.spans.items.clear()


def serve(cell: Cell, reqs, seconds: float):
    """Serve ``reqs`` open loop: (engine, prefill log, window end)."""
    from repro.serving.engine import Request

    sp = cell.spans
    t_open = sp.now()
    clock = lambda: sp.now() - t_open              # noqa: E731
    log = []
    eng = cell.engine(clock, log)
    i = 0
    with sp.span("window"):
        while True:
            now = clock()
            while i < len(reqs) and reqs[i].due <= now:
                r = reqs[i]
                eng.submit(Request(rid=r.rid, prompt=r.prompt,
                                   max_new_tokens=r.max_new, arrival=r.due))
                i += 1
            if eng.waiting or eng.decoding:
                with sp.span("engine_step"):
                    eng.run(until_empty=False)
            elif i < len(reqs):
                with sp.span("wait_arrivals"):
                    time.sleep(max(0.0, reqs[i].due - clock()))
            else:
                break
            if clock() > seconds + DRAIN_S:
                break
    return eng, log, clock()


def latencies(reqs, eng, seconds: float) -> dict:
    """TTFT, TPOT and tokens of every request due, on the wall clock.

    A request that failed or had not finished by the end of the drain
    counts as finishing at that end: a lower bound of its latency.
    """
    done = {r.rid: r for r in eng.finished if not r.failed}
    deadline = seconds + DRAIN_S
    ttft, tpot, tokens, missing = [], [], 0, 0
    for r in reqs:
        e = done.get(r.rid)
        if e is not None and e.first_token_at is not None:
            first, last = e.first_token_at, e.done_at
            tokens += len(r.prompt) + len(e.output)
            n = len(e.output)
        else:
            missing += 1
            first, last, n = deadline, deadline, r.max_new
        ttft.append(first - r.due)
        tpot.append((last - first) / max(n - 1, 1))
    end = max([e.done_at for e in done.values()] + [1e-9])
    return {"ttft": ttft, "tpot": tpot, "tokens": tokens,
            "missing": missing, "end": end}


def sample(finished, seed: int, min_tokens: int = SAMPLE_TOKENS):
    """Requests to check: the longest prompt, then others in an order
    drawn from the seed, until ``min_tokens`` served tokens."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: r.rid)
    longest = max(order, key=lambda r: len(r.prompt))
    rest = [r for r in order if r is not longest]
    rng = np.random.default_rng((seed, 1))
    picked, n = [longest], len(longest.output)
    for j in rng.permutation(len(rest)):
        if n >= min_tokens:
            break
        picked.append(rest[j])
        n += len(rest[j].output)
    return [(r.rid, np.asarray(r.prompt), list(r.output)) for r in picked]


def check(config: dict, seed: int, picked, *, control: bool = False) -> dict:
    """Served tokens against the reference: the share the reference does
    not put first and the widest gap (and the same for the control)."""
    ref = Reference(config, seed)
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
        eng, log, _ = serve(cell, reqs, ctx.seconds)
    compiled = ctx.compiles[0] - compiled
    started = {}                        # request id -> first prefill start
    for rid, t in log:
        started.setdefault(rid, t)
    lat = latencies(reqs, eng, ctx.seconds)
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

    d = flops.dims(ctx.config)
    chunk = ctx.traffic["engine"]["chunk"]
    pre_flops = 0
    for r in reqs:
        if r.rid in started:
            n = len(r.prompt)
            for s in range(0, n, chunk):
                k = min(chunk, n - s)
                pre_flops += flops.span_flops(d, s, k,
                                              head_tokens=int(s + k == n))
    obs = {
        "kind": "serve",
        "spans": list(spans.items),
        "queue_s": [t - r.due for r in reqs if (t := started.get(r.rid))
                    is not None],
        "prefill_s": spans.durations("prefill_call"),
        "decode_s": spans.durations("decode_call"),
        "prefill_flops": pre_flops,
        "peak_flops": ctx.peak["bf16_flops_per_s"],
    }
    finished = [r for r in eng.finished if not r.failed and r.output]
    picked = sample(finished, ctx.seed)
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
