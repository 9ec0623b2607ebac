"""Process-level serving engine: chunked prefill + batched decode.

Implements the scheduling pattern the paper evaluates (S2.2/S8): requests
arrive on a queue (Poisson traces in the benchmarks); prompts are split
into fixed-size *chunks* (paper: 4K) and prefilled batch-by-batch -- the
stage where expert imbalance hurts and where UltraEP balances every chunk
-- then sequences decode in a fixed-slot batch.  The engine records
per-request TTFT/TPOT for the RPS-TTFT curves of Fig. 12.

This is the scheduling layer, not an RPC server (DESIGN.md S8); the model
invocations are pure jitted functions so the same engine drives tiny test
models on CPU and full configs in the dry-run.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.moe.stages import chunk_bounds

__all__ = ["EngineConfig", "Request", "ServingEngine"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (len,) int32
    max_new_tokens: int
    arrival: float = 0.0
    # filled by the engine:
    first_token_at: float | None = None
    done_at: float | None = None
    output: list | None = None
    failed: bool = False            # retired by the fault path, no output


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    chunk_size: int = 4096          # chunked-prefill size (paper: 4K)
    decode_batch: int = 8           # decode slots
    max_seq: int = 8192
    max_retries: int = 1            # model-call retries before a request
    # (prefill) or a decode group is retired as failed -- the engine never
    # stalls on a faulting step (DESIGN.md S13)


class ServingEngine:
    """Drives (prefill_fn, decode_fn) over a request queue.

    prefill_fn(tokens (1, chunk), cache, start) -> (logits, cache)
    decode_fn(tokens (B, 1), caches)            -> (logits, caches)
    new_cache_fn(batch) -> cache pytree

    The engine keeps one cache per active request (prefill) and a batched
    cache for decode slots; a virtual clock advances by the measured or
    supplied per-call latency so TTFT/TPOT statistics work both for real
    execution and for analytic replay.

    While a profiler trace records, the host work opens ``repro.tracing``
    spans: ``engine.schedule`` (one ``run`` iteration), ``engine.prefill``
    (a request) > ``engine.new_cache``, ``engine.prefill_chunk``;
    ``engine.sample`` (device->host read and argmax), ``engine.decode``,
    ``engine.stack_caches``, ``engine.unstack_caches``.  A request's spans
    carry its ``rid``, a decode batch's its rows ``n``.
    """

    def __init__(self, cfg: EngineConfig, *, prefill_fn: Callable,
                 decode_fn: Callable, new_cache_fn: Callable,
                 stack_caches: Callable,
                 unstack_caches: Callable | None = None,
                 clock_fn: Callable | None = None):
        self.cfg = cfg
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.new_cache_fn = new_cache_fn
        self.stack_caches = stack_caches
        self.unstack_caches = unstack_caches or self.unstack
        self.clock_fn = clock_fn
        self.now = 0.0
        self.waiting: deque[Request] = deque()
        self.decoding: list[tuple[Request, object]] = []
        self.finished: list[Request] = []
        # Degraded-fabric accounting (DESIGN.md S13): the engine retries a
        # faulting model call up to cfg.max_retries times, then retires the
        # affected request(s) as failed instead of stalling the queue.
        self.fault_counters = {
            "prefill_retries": 0,
            "decode_retries": 0,
            "failed_requests": 0,
            "nonfinite_logits": 0,
        }

    def submit(self, req: Request):
        self.waiting.append(req)

    def _advance(self, dt: float):
        self.now += dt

    def _fail(self, req: Request):
        req.failed = True
        req.done_at = self.now
        self.fault_counters["failed_requests"] += 1
        self.finished.append(req)

    def _argmax_token(self, row: np.ndarray) -> int:
        """Greedy token with non-finite logits screened.

        NaN logits would make ``argmax`` pick an arbitrary lane; masking
        them keeps decoding deterministic under payload corruption.  A row
        with no finite entry degrades to token 0 (still counted).
        """
        row = np.asarray(row, dtype=np.float64)
        finite = np.isfinite(row)
        if not finite.all():
            self.fault_counters["nonfinite_logits"] += 1
            if not finite.any():
                return 0
            row = np.where(finite, row, -np.inf)
        return int(np.argmax(row))

    def prefill(self, req: Request) -> tuple[object, object]:
        """Prefill ``req.prompt`` chunk by chunk.

        Returns (logits (V,) of the last prompt token, cache).  The last
        chunk is right-padded, so its last real token sits at ``length - 1``
        and not at the end of the chunk.
        """
        with tracing.span("engine.prefill", rid=req.rid):
            with tracing.span("engine.new_cache", rid=req.rid):
                cache = self.new_cache_fn(1)
            last_logits = None
            # Same chunking helper as the MoE overlap driver
            # (repro.moe.stages): fixed-size spans, ragged tail.
            for pos, length in chunk_bounds(
                    len(req.prompt), chunk_size=self.cfg.chunk_size):
                with tracing.span("engine.prefill_chunk", rid=req.rid,
                                  pos=pos):
                    chunk = req.prompt[pos: pos + length]
                    pad = self.cfg.chunk_size - length
                    toks = np.pad(chunk, (0, pad))[None, :]
                    logits, cache = self.prefill_fn(
                        jnp.asarray(toks, jnp.int32), cache, pos, length)
                    last_logits = logits[0, length - 1]
                    self._advance(self.clock_fn() if self.clock_fn else 0.0)
        return last_logits, cache

    def run(self, until_empty: bool = True):
        """Alternate prefill and decode until queues drain.

        Transient model-call faults (``RuntimeError``: the injected
        planner/transfer faults) never escape: the call is retried up to
        ``cfg.max_retries`` times, after which the affected request
        (prefill) or decode group is retired as failed and the queue keeps
        draining.  A device error (``jax.errors.JaxRuntimeError``: out of
        memory, a failed or lost device) is not transient and escapes.
        """
        while self.waiting or self.decoding:
            with tracing.span("engine.schedule"):
                retired = not self._step()
            # A decode group retired on faults goes straight on to the
            # next iteration.
            if not until_empty and not retired:
                break
        return self.finished

    def _step(self) -> bool:
        """One iteration of :meth:`run`: prefill the oldest waiting
        request, then one decode step over the active slots.  False where
        the decode group was retired on faults."""
        # 1. Prefill the oldest waiting request, chunk by chunk.
        if self.waiting:
            req = self.waiting.popleft()
            if self.now < req.arrival:
                self.now = req.arrival
            last_logits = cache = None
            for attempt in range(self.cfg.max_retries + 1):
                try:
                    last_logits, cache = self.prefill(req)
                    break
                except jax.errors.JaxRuntimeError:
                    raise
                except RuntimeError:
                    # Retry the whole prefill; the chunk loop mutates
                    # only local state so a clean restart is safe.
                    if attempt == self.cfg.max_retries:
                        self._fail(req)
                    else:
                        self.fault_counters["prefill_retries"] += 1
            if last_logits is not None:
                req.first_token_at = self.now
                # Host-side scheduling layer (module docstring): reading
                # results back is the point, never under jit.
                with tracing.span("engine.sample", rid=req.rid):
                    first = self._argmax_token(np.asarray(last_logits))  # uep-lint: disable=host-sync
                req.output = [first]
                self.decoding.append((req, cache))

        # 2. One decode step over all active slots (batched).
        if not self.decoding or (len(self.decoding) < self.cfg.decode_batch
                                 and self.waiting):
            return True
        group = self.decoding[: self.cfg.decode_batch]
        n = len(group)
        # A short group is padded with copies of its first row, so
        # decode always runs at one shape and compiles once.
        pad = self.cfg.decode_batch - n
        toks = np.array([[r.output[-1]] for r, _ in group]  # uep-lint: disable=host-sync
                        + [[group[0][0].output[-1]]] * pad, np.int32)
        with tracing.span("engine.stack_caches", n=n):
            caches = self.stack_caches([c for _, c in group]
                                       + [group[0][1]] * pad)
        logits = None
        for attempt in range(self.cfg.max_retries + 1):
            try:
                with tracing.span("engine.decode", n=n):
                    logits, caches = self.decode_fn(jnp.asarray(toks),
                                                    caches)
                break
            except jax.errors.JaxRuntimeError:
                raise
            except RuntimeError:
                if attempt == self.cfg.max_retries:
                    # Retire the whole group: a decode step that
                    # keeps faulting must not wedge the queue.
                    for r, _ in group:
                        self._fail(r)
                    self.decoding = self.decoding[self.cfg.decode_batch:]
                else:
                    self.fault_counters["decode_retries"] += 1
        if logits is None:
            return False
        self._advance(self.clock_fn() if self.clock_fn else 0.0)
        still = []
        with tracing.span("engine.sample", n=n):
            logits_np = np.asarray(logits[:, -1])  # uep-lint: disable=host-sync
            for i, (r, _) in enumerate(group):
                r.output.append(self._argmax_token(logits_np[i]))
                if len(r.output) >= r.max_new_tokens:
                    r.done_at = self.now
                    self.finished.append(r)
                else:
                    still.append(i)
        with tracing.span("engine.unstack_caches", n=n):
            new_caches = self.unstack_caches(caches, n)
        self.decoding = ([(group[i][0], new_caches[i]) for i in still]
                         + self.decoding[self.cfg.decode_batch:])
        return True

    @staticmethod
    def unstack(caches, n):
        return [jax.tree.map(lambda a, i=i: a[i:i + 1]
                             if hasattr(a, "ndim") and a.ndim > 0 else a,
                             caches) for i in range(n)]

    # ------------- metrics -------------

    def ttft(self) -> np.ndarray:
        # Failed (retired) requests never produced a first token; latency
        # statistics cover completed requests only.
        return np.array([r.first_token_at - r.arrival
                         for r in self.finished
                         if not r.failed and r.first_token_at is not None])

    def tpot(self) -> np.ndarray:
        out = []
        for r in self.finished:
            if r.failed or r.first_token_at is None:
                continue
            n = max(len(r.output) - 1, 1)
            out.append((r.done_at - r.first_token_at) / n)
        return np.array(out)
