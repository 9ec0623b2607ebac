"""Readings that set a serving cell's limit: the program's served-token
mismatch share and widest gap, and the control's, on a dozen seeds or
more.

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 ...

For each seed, in one process: the cell as a run sets it up, a window of
``seconds`` at the cell's own load, the same sample of finished requests
a run checks, and then, on the same prompts and served tokens, the
reference in float32 (the program's readings) and the reference with
every matrix product in float8 (the control's readings: the token that
float8 puts first, read against the float32 reference).  Each side's
readings go through the comparison a run's result line makes
(``harness.judge``), which prints ``correct`` for each: the program's has
to come out true and the control's false.  The limit is set between the
largest program reading and the smallest control reading.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import os
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, serve, traffic                  # noqa: E402
from bench.spans import Spans                              # noqa: E402


def readings(config, mix, seed, seconds):
    spans = Spans(trace=False)
    c = serve.Cell(config, mix, seed, spans)
    c.warm_up()
    reqs = traffic.serve_schedule(mix, c.cfg.vocab_size, seed, seconds)
    eng, _, _ = serve.serve(c, reqs, seconds)
    picked = serve.sample([r for r in eng.finished
                           if not r.failed and r.output], seed)
    del eng, c
    gc.collect()
    r = serve.check(config, seed, picked, control=True)
    out = {"seed": seed, "tokens_compared": r["tokens_compared"]}
    for side in ("served", "control"):
        got = {f"served_{k}": r[f"{side}_{k}"]
               for k in ("mismatch_share", "logit_gap")}
        ok, checks = harness.judge(config, got)
        out[side] = {"correct": ok, "readings": got, "checks": checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(ROOT, spec, cell["config"])
    mix = harness.load_traffic(ROOT, cell["traffic"])

    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    for seed in args.seeds:
        print(json.dumps(readings(config, mix, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
