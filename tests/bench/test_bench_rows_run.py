"""The readers of ``ffn_rows_run.{prefill,decode}``: the share of the expert
FFN's capacity rows whose products were computed, from the program's
``rows_run`` counters, on profiles taken here on the CPU."""

import dataclasses
import json
from pathlib import Path
from typing import NamedTuple

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]


def _tiny_cell():
    """The tiny cell's prefill, decode and cache maker, and its chunk."""
    from bench import serve
    from bench.spans import Spans

    tiny = json.loads((ROOT / "tests/bench/data/tiny-moe.json").read_text())
    mix = json.loads((ROOT / "tests/bench/data/tiny-serve.json").read_text())
    prefill, decode, new_cache, _, _ = serve.Cell(tiny, mix, 3,
                                                  Spans(False)).fns
    return prefill, decode, new_cache, mix["engine"]["chunk"]


class _CountersWithoutRows(NamedTuple):
    held: object
    drops: object


@pytest.fixture(scope="module")
def rows_run_readings(tmp_path_factory):
    """Each rows-run reader's reading (prefill, decode): before any call,
    from a program whose counters have no rows run, and after one prefill
    and one decode call of the tiny cell."""
    import jax.numpy as jnp

    from bench import harness
    from repro import tracing

    tmp = tmp_path_factory.mktemp("rows_run")
    obs = {"kind": "serve"}
    names = [f"ffn_rows_run.{k}" for k in ("prefill", "decode")]

    def read():
        return [harness.read_metric(ROOT, n, obs) for n in names]

    prefill, decode, new_cache, chunk = _tiny_cell()
    toks = jnp.ones((1, chunk), jnp.int32)
    got = {"none": read()}
    try:
        with jax.profiler.trace(str(tmp / "old")):
            zero = jnp.zeros((2,), jnp.int32)
            for k in ("prefill", "decode"):
                tracing.count(k, _CountersWithoutRows(zero, zero), (0, 64),
                              (0, 8))
        got["old"] = read()
        tracing.reset()
        with jax.profiler.trace(str(tmp / "new")):
            _, caches = prefill(toks, new_cache(1), 0, chunk)
            decode(toks[:, :1], caches)
        got["new"] = read()
    finally:
        tracing.reset()
    return got


@pytest.mark.parametrize("i", [0, 1], ids=["prefill", "decode"])
def test_ffn_rows_run_reads_the_program_counters(rows_run_readings, i):
    """On the CPU the expert FFN runs densely, every capacity row of every
    slot: after a prefill and a decode call of the tiny cell each reader
    gives 100.  With no call of its kind, or from a program whose counters
    have no rows run, it gives nothing."""
    got = rows_run_readings
    assert got["none"][i] is None and got["old"][i] is None
    assert got["new"][i] == 100.0


def test_ffn_rows_run_reads_the_kernels_row_tiles(tmp_path, monkeypatch,
                                                 tpu_interpret):
    """The count-aware kernel (here in the TPU interpreter) runs only each
    slot's filled row tiles: a prefill runs at least the rows of the pairs
    its slots hold, and fewer than all capacity rows (the tiny cell's two
    replica slots hold none)."""
    import jax.numpy as jnp

    from bench import harness, model
    from repro import tracing

    rcfg = model.runtime_config
    monkeypatch.setattr(model, "runtime_config", lambda c, **kw: (
        dataclasses.replace(rcfg(c, **kw), use_kernel=True)))
    obs = {"kind": "serve"}
    try:
        prefill, _, new_cache, chunk = _tiny_cell()
        toks = jnp.ones((1, chunk), jnp.int32)
        prefill(toks, new_cache(1), 0, chunk)
        with jax.profiler.trace(str(tmp_path)):
            prefill(toks, new_cache(1), 0, chunk)
        fill = harness.read_metric(ROOT, "ffn_fill.prefill", obs)
        v = harness.read_metric(ROOT, "ffn_rows_run.prefill", obs)
    finally:
        tracing.reset()
    assert 0 < fill <= v < 100
