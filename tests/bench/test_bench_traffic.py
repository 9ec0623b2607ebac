"""The traffic generator: deterministic per seed, the same schedule for
every seed, and the copied domain-drift stream."""

import json
from pathlib import Path

import numpy as np

from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
MIX = json.loads((ROOT / "tests/bench/data/tiny-serve.json").read_text())
BIG = 2**31 + 977


def test_schedule_is_a_function_of_the_seed():
    a = traffic.serve_schedule(MIX, 512, BIG, 10.0)
    b = traffic.serve_schedule(MIX, 512, BIG, 10.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.serve_schedule(MIX, 512, BIG + 1, 10.0)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_gets_the_same_work_in_another_order():
    """The same sizes at the same times for every seed; only the tokens
    change."""
    a = traffic.serve_schedule(MIX, 512, 1, 10.0)
    b = traffic.serve_schedule(MIX, 512, 2, 10.0)
    assert len(a) == len(b) == round(MIX["arrivals"]["rate_rps"] * 10)
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == [
        (r.due, len(r.prompt), r.max_new) for r in b]
    assert [r.due for r in a][0] == 0.0
    assert max(r.due for r in a) < 10.0
    # Poisson-like: the gaps are not all alike, and the lengths vary.
    gaps = np.diff([r.due for r in a])
    assert gaps.max() > 3 * gaps.min()
    assert len({len(r.prompt) for r in a}) > 5


def test_lengths_follow_the_mix():
    q = traffic.quantiles({"dist": "lognormal", "median": 1024,
                           "sigma": 0.8, "min": 128, "max": 8192}, 101)
    assert q.min() >= 128 and q.max() <= 8192 and np.median(q) == 1024
    u = traffic.quantiles({"dist": "uniform", "min": 4, "max": 16}, 130)
    assert sorted(set(u.tolist())) == list(range(4, 17))


def test_domain_drift_copy():
    s = traffic.DomainDrift(1000, seed=3, drift_period=64, switch_period=50)
    m = s.mixture(10)
    assert np.isclose(m.sum(), 1.0) and (m > 0).all()
    # A hard switch puts one domain on top for a window.
    assert s.mixture(50).argmax() == 1
    x = s.sequences(7, [300, 300])
    y = s.sequences(7, [300, 300])
    assert all(np.array_equal(p, q) for p, q in zip(x, y))
    # Zipf: the most frequent token of a long draw is far above uniform.
    counts = np.bincount(np.concatenate(s.sequences(1, [20000])),
                         minlength=1000)
    assert counts.max() > 20 * 20000 / 1000

