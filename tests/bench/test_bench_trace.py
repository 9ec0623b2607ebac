"""The trace reduction: busy time and idle share per device, collective
time by kind, idle gaps named by the host span open over them, and the
breakdown lists.  On a small trace recorded on a v5e and on a made-up
two-chip trace whose answers are known by hand."""

import json
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event

ROOT = Path(__file__).resolve().parents[2]
H = "/host:CPU"
D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


def _made_up():
    ev = [Event(H, "python", "bench.window", 0.0, 10.0),
          Event(H, "python", "bench.engine_step", 0.0, 6.0),
          Event(H, "python", "bench.prefill_call", 0.5, 3.0),
          Event(H, "python", "bench.wait_arrivals", 6.0, 10.0),
          Event(D0, MODS, "jit_step(1)", 1.0, 3.0),
          Event(D0, OPS, "%fusion.1 = bf16[8,128]{1,0} fusion(x)", 1.0, 2.0),
          Event(D0, OPS, "%all-to-all.2 = bf16[4,8]{1,0} all-to-all(y)",
                1.5, 2.5),
          Event(D0, OPS, "%all-reduce-start.3 = f32[8] all-reduce-start(z)",
                2.5, 2.6),
          Event(D0, OPS, "%all-reduce-done.3 = f32[8] all-reduce-done(z)",
                2.6, 2.7),
          Event(D0, OPS, "%fusion.1 = bf16[8,128]{1,0} fusion(x)", 7.0, 8.0),
          Event(D1, OPS, "%fusion.1 = bf16[8,128]{1,0} fusion(x)", 1.0, 1.5),
          Event(D1, OPS, "%fusion.1 = bf16[8,128]{1,0} fusion(x)", 11.0,
                12.0)]       # after the window: clipped away
    return ev


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                               (3, 4)]


def test_busy_and_idle_per_device():
    r = trace.reduce_trace(_made_up())
    assert r["window_s"] == 10.0
    assert r["devices"][D0]["busy_s"] == pytest.approx(2.7)  # 1-2.7, 7-8
    assert r["devices"][D1]["busy_s"] == pytest.approx(0.5)
    assert r["busy_s"] == pytest.approx((2.7 + 0.5) / 2)
    assert r["busiest"] == D0


def test_collectives_by_kind():
    r = trace.reduce_trace(_made_up())
    c = r["devices"][D0]["collective_s"]
    assert c["all-to-all"] == pytest.approx(1.0)
    assert c["all-reduce"] == pytest.approx(0.2)
    assert set(c) == {"all-to-all", "all-reduce"}
    assert trace.op_kind("%fusion.1 = f32[2] fusion(a)") is None
    assert trace.op_kind("%reduce-scatter.7 = f32[2] reduce-scatter(a)") \
        == "reduce-scatter"
    assert trace.op_kind("%collective-permute-start.1 = (f32[2]) "
                         "collective-permute-start(a)") == "collective-permute"


def test_idle_gaps_named_by_the_host_span():
    r = trace.reduce_trace(_made_up())
    gaps = dict(r["breakdown"]["idle_gaps"])
    # Idle 0-1, 2.7-7 and 8-10: 0-0.5 and 3-6 in engine_step, 0.5-1 and
    # 2.7-3 in prefill_call, 6-7 and 8-10 in wait_arrivals.
    assert gaps["wait_arrivals"] == pytest.approx(3.0)
    assert gaps["engine_step"] == pytest.approx(3.5)
    assert gaps["prefill_call"] == pytest.approx(0.8)
    assert sum(gaps.values()) == pytest.approx(10.0 - 2.7)


def test_top_ops_named_by_program_and_instruction():
    ops = trace.reduce_trace(_made_up())["breakdown"]["device_ops"]
    assert ops[0] == ["jit_step:fusion.1 bf16[8,128]", pytest.approx(1.0)]
    assert ["?:fusion.1 bf16[8,128]", pytest.approx(1.0)] in ops
    assert len(ops) <= 10


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_trace([e for e in _made_up()
                            if e.name != "bench.window"])
    with pytest.raises(ValueError):
        trace.reduce_trace([e for e in _made_up() if e.plane == H])


RECORDED = ROOT / "tests/bench/data/trace_small.json"


def test_recorded_v5e_trace():
    """Three steps of two jitted calls, recorded on one v5e with the
    benchmark's spans (engine_step > prefill_call, decode_call; then
    wait_arrivals of 5 ms)."""
    ev = [Event(*e) for e in json.loads(RECORDED.read_text())]
    r = trace.reduce_trace(ev)
    assert list(r["devices"]) == [D0]
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["breakdown"]["idle_gaps"])
    # Each step sleeps 5 ms with the device idle.
    assert gaps["wait_arrivals"] >= 3 * 0.005 * 0.9
    assert r["devices"][D0]["collective_s"] == {}
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert all(n.startswith("jit_") for n in names)
