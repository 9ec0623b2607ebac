"""Chip smoke test: the main paths at qwen3-235b-a22b's published widths.

    python chip_smoke.py                # serving on one chip
    python chip_smoke.py --four-chips   # EP=4 training on a 2x2 v5e host

Default phase: ``repro.launch.serve.serve_trace`` serves seeded requests
through the chunked-prefill engine on one chip: one layer (one whole period),
all 128 experts on the chip (EP=1), full vocab, bf16 weights, the ultraep
balancer.  Every request must complete with no retry, no failed request and
no non-finite logit, and the engine's first-token logits for one prompt
must match ``models.model.forward`` on the same params.

``--four-chips``: ``repro.launch.train.train`` takes a few steps on a
(data=1, model=4) mesh with flat EP=4 all-to-all, 32 of the 128 experts
plus 2 replica slots per chip, bf16 + Adafactor, 4 sequences of 4096
tokens; then the same steps from the same seed with ``balancer="none"``.
Both runs must have finite losses, no supervisor restart and no dropped
token, and their first-step loss and gradient norm must agree.

Everything runs in this one process.  The script fails, and prints no
result, where JAX finds no TPU.  Its last line is one JSON object naming
the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-235b-a22b"
SERVE = dict(num_layers=1, dtype="bfloat16", balancer="ultraep",
             requests=8, rps=4.0, chunk=512, max_new=16,
             prompt_len=(512, 2049), seed=0)
# A randomly initialised router sends most tokens of a long sequence to the
# same few experts: the attention output, shared by all positions of a
# prefix, outweighs the 0.02-scale token embeddings.  So the 4 sequences run
# as 4 microbatches of one, and each slot holds every token of a microbatch:
# 1024 tokens x top-8 = 8192 items per chip, 32 + 2 slots per chip,
# ceil(8192 * 17 / 34) = 4096.  Nothing can drop, whatever the routing, and
# the two balancers must compute the same function.
TRAIN = dict(num_layers=1, dtype="bfloat16", steps=3, batch=4, seq=4096,
             microbatches=4, cf_slot=17.0, lr=1e-4, seed=0, log_every=1)

# First-token logits, engine (chunked prefill through the KV cache) vs
# forward (whole prompt, no cache), both in bf16.  The two paths block
# attention differently and round their bf16 activations at different
# points, so the residual stream differs by a few bf16 roundings (2^-9
# relative each), which moves a logit by about 1% of the logits' RMS.  A
# wrong path (another position, a lost token, a misrouted expert) gives
# uncorrelated logits, off by about 1.4x their RMS.  The bound sits between.
LOGITS_TOL_RMS = 0.1
# First training step, ultraep vs none, drops == 0: balancing moves which
# slot computes a token, not the mathematics.  What differs is the order
# of the bf16 combine sums, a few roundings per token; the loss averages
# 16384 tokens and the gradient norm all parameters.
LOSS_RTOL = 1e-3
GNORM_RTOL = 2e-2


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def _peaks(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices]


def serve_phase(**overrides) -> None:
    import jax
    import numpy as np

    from repro.launch.serve import serve_trace
    from repro.models.model import forward
    from repro.models.transformer import ParallelCtx
    from repro.serving.engine import Request

    kw = {**SERVE, **overrides}
    t0 = time.perf_counter()
    served = serve_trace(ARCH, **kw)
    eng, params, cfg, rcfg = (served.engine, served.params, served.cfg,
                              served.rcfg)
    leaves = jax.tree.leaves(params)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"experts={cfg.moe.num_experts} top_k={cfg.moe.top_k} "
          f"expert_d_ff={cfg.moe.d_ff} vocab={cfg.vocab_size} "
          f"dtype={rcfg.dtype.name}")
    print(f"params: {sum(x.size for x in leaves):,} "
          f"({sum(x.nbytes for x in leaves):,} bytes)")
    print(f"warm-up (requests that compile or load the engine's path): "
          f"{served.warmup_s:.1f}s; phase so far {time.perf_counter()-t0:.1f}s")
    done = sorted(eng.finished, key=lambda r: r.rid)
    for r in done:
        print(f"request {r.rid}: prompt {len(r.prompt)} tokens, "
              f"TTFT {(r.first_token_at - r.arrival) * 1e3:.1f} ms, "
              f"{len(r.output or [])} tokens out")
    print(f"fault_counters: {eng.fault_counters}")
    if len(done) != kw["requests"] or any(
            r.failed or len(r.output) != kw["max_new"] for r in done):
        _fail("not every request completed")
    if any(eng.fault_counters.values()):
        _fail(f"fault counters not all zero: {eng.fault_counters}")

    # The engine's first-token logits vs a plain forward on the same params,
    # for one chunk of a served prompt.  One chunk gives both paths the
    # same tokens per MoE call, so the same capacities and, routing being
    # the same up to rounding, the same drops.  With one layer the last
    # position's logits depend on its own MoE output only, so drops of
    # other tokens (random routers route unevenly) cannot move them.
    prompt = done[0].prompt[:kw["chunk"]]
    row, _ = eng.prefill(Request(rid=-1, prompt=prompt, max_new_tokens=1))
    fwd = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg, rcfg,
                                       ParallelCtx(mesh=None)))
    t1 = time.perf_counter()
    logits, _, drops, _ = fwd(params, prompt[None])
    ref = np.asarray(logits[0, -1], np.float64)
    print(f"forward compile + run: {time.perf_counter() - t1:.1f}s; "
          f"forward drops {int(drops)} of {len(prompt) * cfg.moe.top_k} "
          f"routed (token, expert) pairs")
    got = np.asarray(row, np.float64)
    rms = float(np.sqrt(np.mean(ref ** 2)))
    err = float(np.abs(got - ref).max())
    print(f"logits check ({len(prompt)}-token prompt): max |engine - "
          f"forward| = {err:.4g}, logits RMS {rms:.4g}, bound "
          f"{LOGITS_TOL_RMS} x RMS = {LOGITS_TOL_RMS * rms:.4g}")
    if not (np.isfinite(got).all() and err <= LOGITS_TOL_RMS * rms):
        _fail("engine first-token logits disagree with forward")


def train_phase(mesh, **overrides) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from repro.launch.train import compile_step, train

    kw = {**TRAIN, **overrides}
    runs = {}
    # The none run's step compiles in a thread while ultraep compiles and
    # trains; the none run then loads it from the persistent compile cache.
    with ThreadPoolExecutor(max_workers=1) as pool:
        pending = pool.submit(
            compile_step, ARCH, balancer="none", mesh=mesh,
            **{k: v for k, v in kw.items() if k != "log_every"})
        for balancer in ("ultraep", "none"):
            if balancer == "none":
                print(f"none: step compiled alongside ultraep in "
                      f"{pending.result():.1f}s")
            t0 = time.perf_counter()
            r = train(ARCH, balancer=balancer, mesh=mesh, **kw)
            runs[balancer] = r
            print(f"{balancer}: params {r.params:,}; param bytes per chip "
                  f"{r.param_bytes}; first step (compile or cache load + "
                  f"run) {r.first_step_s:.1f}s; run "
                  f"{time.perf_counter() - t0:.1f}s")
            print(f"{balancer}: losses {r.losses} grad_norms {r.grad_norms} "
                  f"drops {r.drops} restarts {r.restarts}")
            print(f"{balancer}: peak_bytes_in_use per chip "
                  f"{_peaks(mesh.devices.flat)}")
            if not np.isfinite(r.losses).all():
                _fail(f"{balancer}: non-finite loss")
            if r.restarts:
                _fail(f"{balancer}: supervisor restarted {r.restarts} times")
            if any(r.drops):
                _fail(f"{balancer} dropped tokens: {r.drops}")
    u, n = runs["ultraep"], runs["none"]
    dl = abs(u.losses[0] - n.losses[0]) / abs(n.losses[0])
    dg = abs(u.grad_norms[0] - n.grad_norms[0]) / abs(n.grad_norms[0])
    print(f"first step, ultraep vs none: loss rel diff {dl:.3g} (bound "
          f"{LOSS_RTOL}), grad norm rel diff {dg:.3g} (bound {GNORM_RTOL})")
    if dl > LOSS_RTOL or dg > GNORM_RTOL:
        _fail("ultraep and none disagree on the first step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only EP=4 training on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache
    from repro.launch.mesh import make_test_mesh

    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        if len(devices) < 4:
            _fail(f"--four-chips needs 4 chips, JAX found {len(devices)}")
        train_phase(make_test_mesh(data=1, model=4))
    else:
        serve_phase()
    print(f"peak_bytes_in_use: {_peaks(devices)}")
    print(f"phase seconds: {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
