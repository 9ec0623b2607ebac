"""Traffic from a mix's parameters and a seed.

One generator reads every mix file.  Sizes and arrival gaps are evenly
spaced quantiles of the mix's distributions, in one fixed order, so
every seed gets the same schedule; the seed picks the tokens.  A run's
work and its bursts are then the same from seed to seed, and the seeds
differ in what they route.

Tokens come from a copy of the program's domain-drift stream
(``repro.data.pipeline.SyntheticLMStream``): each domain is a Zipf source
over its own permutation of the vocabulary, and the mix of domains drifts
smoothly with the step and switches hard every ``switch_period`` steps.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import NamedTuple

import numpy as np

__all__ = ["DomainDrift", "Request", "quantiles", "serve_schedule"]

SCHEDULE_SEED = 20250801       # orders the one schedule of each mix


class DomainDrift:
    """Seeded Zipf domains whose mixture drifts with the step."""

    def __init__(self, vocab: int, *, seed: int, num_domains: int = 4,
                 zipf_a: float = 1.3, drift_period: int = 64,
                 switch_period: int = 50):
        self.vocab, self.num_domains = vocab, num_domains
        self.drift_period, self.switch_period = drift_period, switch_period
        self.seed = seed
        rng = np.random.default_rng(seed)
        self._perms = [rng.permutation(vocab) for _ in range(num_domains)]
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        pmf = ranks ** (-zipf_a)
        self._cdf = np.cumsum(pmf / pmf.sum())

    def mixture(self, step: int) -> np.ndarray:
        t = 2 * np.pi * (step % self.drift_period) / self.drift_period
        base = 1.0 + np.cos(t + np.arange(self.num_domains)
                            * 2 * np.pi / self.num_domains)
        dom = (step // self.switch_period) % self.num_domains
        base[dom] += 2.0 * ((step // self.switch_period) % 2)
        return base / base.sum()

    def draw(self, rng: np.random.Generator, domain: int, shape
             ) -> np.ndarray:
        u = rng.random(shape)
        ranks = np.minimum(np.searchsorted(self._cdf, u), self.vocab - 1)
        return self._perms[domain][ranks].astype(np.int32)

    def sequences(self, step: int, lengths, *, stream: int = 0
                  ) -> list[np.ndarray]:
        """One sequence per length, each of one domain drawn from the
        mixture at ``step``."""
        rng = np.random.default_rng((self.seed, stream, step))
        doms = rng.choice(self.num_domains, size=len(lengths),
                          p=self.mixture(step))
        return [self.draw(rng, int(d), int(n)) for d, n in zip(doms, lengths)]


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, as ints.

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (both ends included).
    """
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + p * (dist["max"] + 1 - dist["min"]) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


class Request(NamedTuple):
    rid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray
    max_new: int


def serve_schedule(mix: dict, vocab: int, seed: int, seconds: float
                   ) -> list[Request]:
    """The requests due in ``[0, seconds)``, open loop.

    One schedule per mix and window: ``round(rate * seconds)`` requests,
    whose gaps are the quantiles of an exponential distribution (Poisson
    arrivals) scaled to fill the window, and whose prompt and output
    lengths are quantiles of the mix's distributions, each list in an
    order drawn once by a fixed generator.  Every seed gets that one
    schedule, so every run offers the same bursts at the same times; the
    seed draws the tokens.  (A seed that moved the bursts would move the
    tails with them: which requests a burst holds decides how many
    decode steps wait behind whole-prompt prefills.)
    """
    rate = mix["arrivals"]["rate_rps"]
    n = max(1, round(rate * seconds))
    fixed = np.random.default_rng(SCHEDULE_SEED)
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p)
    gaps = fixed.permutation(gaps * seconds / gaps.sum())
    prompts = fixed.permutation(quantiles(mix["prompt_len"], n))
    outs = fixed.permutation(quantiles(mix["output_len"], n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    t = mix["tokens"]
    stream = DomainDrift(vocab, seed=seed, num_domains=t["num_domains"],
                         zipf_a=t["zipf_a"], drift_period=t["drift_period"],
                         switch_period=t["switch_period"])
    reqs = []
    for i in range(n):
        (toks,) = stream.sequences(i, [prompts[i]], stream=1)
        reqs.append(Request(i, float(due[i]), toks, int(outs[i])))
    return reqs

