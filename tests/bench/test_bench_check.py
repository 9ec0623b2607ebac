"""What decides ``correct`` in a serving cell, at a size a test run holds.

The control (the reference with every matrix product in float8) must read
above the limit where the program reads below it; and a run whose timed
path is broken underneath must come out not correct: a served token
altered where it is produced, a decode step that hands back its cache
unchanged, half of a decode batch left out.  The harness's look for a
chip is skipped; the rest of a run is driven as ``bench/run.py`` drives
it.  The chip's readings, at the cell's own size, are in PERF.md.
"""

import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, serve

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "tests/bench/data/tiny-moe.json").read_text())
MIX = json.loads((ROOT / "tests/bench/data/tiny-serve.json").read_text())
SEED = 2**31 + 5
LIMIT = TINY["check"]["served_mismatch_share"]


def _run(fault=None, *, control=False):
    ctx = harness.Context(
        cell={"name": "tiny", "chips": 1}, config=TINY, traffic=MIX,
        seed=SEED, seconds=2.0, trace=False, t_start=time.perf_counter(),
        peak={"bf16_flops_per_s": 197e12}, devices=jax.devices()[:1])
    real = serve.Cell.__init__

    def broken(self, *a, **k):
        real(self, *a, **k)
        if fault is not None:
            p, d, *rest = self.fns
            self.fns = (p, fault(d), *rest)

    serve.Cell.__init__ = broken
    try:
        if control:
            return ctx, serve
        res = serve.run(ctx)
    finally:
        serve.Cell.__init__ = real
    spec = {"end_to_end": [], "per_layer": []}
    return harness.result_line(ROOT, spec, ctx.cell, ctx, res), res


def test_sound_run_is_correct():
    line, res = _run()
    assert line["correct"], line["checks"]
    assert res["attempted"] == round(MIX["arrivals"]["rate_rps"] * 2.0)
    assert line["checks"]["served_mismatch_share"]["limit"] == LIMIT


def test_control_reads_above_the_limit():
    from bench import control

    r = control.readings(TINY, MIX, SEED, 2.0)
    assert r["served"]["correct"], r
    assert not r["control"]["correct"], r
    share = "served_mismatch_share"
    assert (r["served"]["readings"][share] <= LIMIT
            < r["control"]["readings"][share])


def _token_altered(decode):
    def f(tokens, caches):
        logits, new = decode(tokens, caches)
        return logits.at[..., 0].set(1e4), new
    return f


def _state_unchanged(decode):
    def f(tokens, caches):
        logits, _ = decode(tokens, caches)
        return logits, caches
    return f


def _half_batch(decode):
    def f(tokens, caches):
        logits, new = decode(tokens, caches)
        return jax.numpy.repeat(logits[::2], 2, axis=0), new
    return f


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(fault):
    line, _ = _run(fault)
    assert not line["correct"], line["checks"]
    assert line["checks"]["served_mismatch_share"]["value"] > LIMIT
