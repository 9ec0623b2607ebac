"""Serving engine: chunked prefill batching, decode slots, metrics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.core.balancer import BalancerConfig
from repro.models.model import init_lm
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.serving.adapter import make_engine_fns
from repro.serving.engine import EngineConfig, Request, ServingEngine


@pytest.mark.parametrize("arch", ["tiny-moe", "tiny-mla-moe"])
def test_engine_end_to_end(arch):
    cfg = get_config(arch)
    rcfg = RuntimeConfig(balancer=BalancerConfig(mode="ultraep", n_slot=2),
                         cf_pair=8, cf_slot=8, remat=False)
    pctx = ParallelCtx(mesh=None)
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx)
    max_seq = 128
    prefill, decode, new_cache, stack, unstack = make_engine_fns(
        params, cfg, rcfg, pctx, max_seq=max_seq)
    eng = ServingEngine(EngineConfig(chunk_size=16, decode_batch=2,
                                     max_seq=max_seq),
                        prefill_fn=prefill, decode_fn=decode,
                        new_cache_fn=new_cache, stack_caches=stack,
                        unstack_caches=unstack,
                        clock_fn=lambda: 0.001)
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               size=int(rng.integers(8, 40)))
                           .astype(np.int32),
                           max_new_tokens=4, arrival=i * 0.01))
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.output) == 4 for r in done)
    assert (eng.ttft() >= 0).all()
    assert (eng.tpot() > 0).all()


def test_engine_overlap_chunks_identical_outputs():
    """MoE overlap chunking inside chunked prefill (overlap_chunks=2 over
    the 16-token prefill chunk) must not change a single sampled token:
    the staged driver is bit-identical at the engine's capacities."""
    cfg = get_config("tiny-moe")
    outs = {}
    for overlap in (1, 2):
        rcfg = RuntimeConfig(balancer=BalancerConfig(mode="ultraep",
                                                     n_slot=2),
                             cf_pair=8, cf_slot=8, remat=False,
                             overlap_chunks=overlap)
        pctx = ParallelCtx(mesh=None)
        params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx)
        prefill, decode, new_cache, stack, unstack = make_engine_fns(
            params, cfg, rcfg, pctx, max_seq=128)
        eng = ServingEngine(EngineConfig(chunk_size=16, decode_batch=2,
                                         max_seq=128),
                            prefill_fn=prefill, decode_fn=decode,
                            new_cache_fn=new_cache, stack_caches=stack,
                            unstack_caches=unstack)
        rng = np.random.default_rng(7)
        for i in range(3):
            eng.submit(Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab_size, size=24)
                .astype(np.int32),
                max_new_tokens=4))
        outs[overlap] = [r.output for r in sorted(eng.run(),
                                                  key=lambda r: r.rid)]
    assert outs[1] == outs[2]


@pytest.mark.parametrize("decode_batch", [1, 3])
def test_engine_prefill_decode_greedy_consistency(decode_batch):
    """Greedy continuation via the engine == greedy continuation via
    sequential full forwards; a decode group shorter than the decode batch
    is padded, so decode runs at one shape and the padding changes
    nothing."""
    from repro.models.model import forward

    cfg = get_config("tiny-dense")
    rcfg = RuntimeConfig(balancer=BalancerConfig(mode="none", n_slot=2),
                         remat=False)
    pctx = ParallelCtx(mesh=None)
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx)
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (24,), 0,
                           cfg.vocab_size), np.int32)

    prefill, decode, new_cache, stack, unstack = make_engine_fns(
        params, cfg, rcfg, pctx, max_seq=64)
    shapes = set()

    def decode_seen(toks, caches):
        shapes.add(toks.shape)
        return decode(toks, caches)

    eng = ServingEngine(EngineConfig(chunk_size=8, decode_batch=decode_batch,
                                     max_seq=64),
                        prefill_fn=prefill, decode_fn=decode_seen,
                        new_cache_fn=new_cache, stack_caches=stack,
                        unstack_caches=unstack)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    done = eng.run()
    out_engine = done[0].output
    assert shapes == {(decode_batch, 1)}

    # Reference: greedy next-token via repeated full forwards.
    toks = list(prompt)
    out_ref = []
    for _ in range(4):
        batch = {"tokens": jnp.asarray(np.array(toks)[None])}
        logits, *_ = forward(params, batch, cfg, rcfg, pctx)
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        out_ref.append(nxt)
        toks.append(nxt)
    assert out_engine == out_ref


def test_engine_first_token_from_ragged_last_chunk():
    """A prompt that is not a multiple of the chunk: the first token comes
    from the last real prompt token, not from the padding after it."""
    from repro.models.model import forward

    cfg = get_config("tiny-dense")
    rcfg = RuntimeConfig(balancer=BalancerConfig(mode="none", n_slot=2),
                         remat=False)
    pctx = ParallelCtx(mesh=None)
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (21,), 0, cfg.vocab_size), np.int32)
    prefill, decode, new_cache, stack, unstack = make_engine_fns(
        params, cfg, rcfg, pctx, max_seq=64)
    eng = ServingEngine(EngineConfig(chunk_size=8, decode_batch=1,
                                     max_seq=64),
                        prefill_fn=prefill, decode_fn=decode,
                        new_cache_fn=new_cache, stack_caches=stack,
                        unstack_caches=unstack)
    row, _ = eng.prefill(Request(rid=0, prompt=prompt, max_new_tokens=1))
    ref = forward(params, {"tokens": jnp.asarray(prompt[None])}, cfg, rcfg,
                  pctx)[0][0, -1]
    np.testing.assert_allclose(np.asarray(row), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
    assert eng.run()[0].output[0] == int(np.argmax(np.asarray(ref)))


def test_depth_cut_keeps_published_widths():
    from repro.configs.reduce import depth_cut

    full = get_config("qwen3-235b-a22b")
    cut = depth_cut(full, 1)
    assert cut.num_layers == 1
    assert (cut.d_model, cut.vocab_size, cut.moe) == (
        full.d_model, full.vocab_size, full.moe)
    assert depth_cut(full, None) is full
    with pytest.raises(ValueError):
        depth_cut(get_config("jamba-v0.1-52b"), 3)   # not whole periods


def test_serve_trace_reduced_end_to_end():
    from repro.launch.serve import serve_trace

    served = serve_trace("qwen3-235b-a22b", reduce=True, requests=4,
                         chunk=16, max_new=3, prompt_len=(10, 40),
                         dtype="bfloat16")
    done = served.engine.finished
    assert len(done) == 4 and all(len(r.output) == 3 for r in done)
    assert not any(served.engine.fault_counters.values())
    assert (served.engine.ttft() > 0).all()     # measured, not virtual
    assert served.params.embedding.dtype == jnp.bfloat16
