"""The expert-share runner's check catches a broken timed path: a served
token altered where it is produced, and a decode step that hands back its
cache unchanged, come out not correct."""

import json
import time
from pathlib import Path

import jax
import pytest

from bench import harness, serve_share

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "tests/bench/data/tiny-share.json").read_text())
MIX = json.loads((ROOT / "tests/bench/data/tiny-serve.json").read_text())
SEED = 2**31 + 6
LIMIT = TINY["check"]["served_mismatch_share"]


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


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    real = serve_share.Cell.__init__

    def broken(self, *a, **k):
        real(self, *a, **k)
        p, d, *rest = self.fns
        self.fns = (p, fault(d), *rest)

    monkeypatch.setattr(serve_share.Cell, "__init__", broken)
    ctx = harness.Context(
        cell={"name": "tiny-share", "chips": 1}, config=TINY, traffic=MIX,
        seed=SEED, seconds=2.0, trace=False, t_start=time.perf_counter(),
        peak={"bf16_flops_per_s": 197e12}, devices=jax.devices()[:1])
    res = serve_share.run(ctx)
    line = harness.result_line(ROOT, {"end_to_end": [], "per_layer": []},
                               ctx.cell, ctx, res)
    assert not line["correct"], line["checks"]
    assert line["checks"]["served_mismatch_share"]["value"] > LIMIT
