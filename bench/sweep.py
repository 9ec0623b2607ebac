"""Find a serving cell's knee: the highest rate it sustains.

    python bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 1.0 1.5 2.0 ...

One process sets the cell up once, then serves a window at each rate in
turn (the cell's mix with only ``rate_rps`` replaced) and prints, per
rate, the TTFT median and 90th percentile, the tokens per second, and the
backlog: requests due in the window that had no first token when it
closed.  Past the knee the backlog grows with the window.  The cell's
rate is then fixed in its traffic file; the benchmark never searches.
"""

from __future__ import annotations

import argparse
import os
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, serve, traffic                  # noqa: E402
from bench.spans import Spans                              # noqa: E402


def _pct(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def sweep(config, mix, seed, seconds, rates):
    """One row per rate, until the first rate at which the device runs out
    of memory (the engine admits every prompt, and past the knee the
    caches of admitted requests pile up)."""
    import jax

    c = serve.Cell(config, mix, seed, Spans(trace=False))
    c.warm_up()
    for rate in rates:
        m = dict(mix, arrivals=dict(mix["arrivals"], rate_rps=rate))
        reqs = traffic.serve_schedule(m, c.cfg.vocab_size, seed, seconds)
        c.mix = m
        try:
            eng, _, t_end = serve.serve(c, reqs, seconds)
        except jax.errors.JaxRuntimeError as e:
            yield {"rate_rps": rate, "requests": len(reqs),
                   "error": str(e).splitlines()[0][:200]}
            return
        lat = serve.latencies(reqs, eng, seconds)
        first = {r.rid: r.first_token_at for r in eng.finished}
        backlog = sum(1 for r in reqs
                      if (first.get(r.rid) or 1e18) > seconds)
        yield {"rate_rps": rate, "requests": len(reqs),
               "ttft_p50_ms": _pct(lat["ttft"], 50) * 1e3,
               "ttft_p90_ms": _pct(lat["ttft"], 90) * 1e3,
               "tpot_p90_ms": _pct(lat["tpot"], 90) * 1e3,
               "tokens_per_s": lat["tokens"] / lat["end"],
               "backlog_at_close": backlog, "missing": lat["missing"],
               "drain_end_s": t_end}
        del eng
        time.sleep(1.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(ROOT, spec, cell["config"])
    mix = harness.load_traffic(ROOT, cell["traffic"])

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    for row in sweep(config, mix, args.seed, args.seconds, args.rates):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
