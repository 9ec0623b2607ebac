"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

The cell, its configuration, its traffic mix and its metrics are found by
name: ``BENCHMARK.json`` names the cell's configuration file and traffic
mix; the mix is ``bench/traffic/<traffic>.json`` and its ``kind`` names
the module that runs it (``bench/<kind>.py``); a per-layer metric ``<m>`` is
read by ``bench/metrics/<m>.py``.  With ``--trace 0`` the result holds the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from the benchmark's spans and the profiler's trace of the window.

The run fails, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's compile cache lives in
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import importlib                                           # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import sys                                                 # noqa: E402
from pathlib import Path                                   # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# The TPU runtime would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness                                  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(ROOT, spec, cell["config"])
    mix = harness.load_traffic(ROOT, cell["traffic"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT)
    ctx = harness.Context(
        cell=cell, config=config, traffic=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        peak=harness.peak(ROOT, devices[0].device_kind),
        devices=devices[:cell["chips"]], compiles=harness.count_compiles())
    runner = importlib.import_module(f"bench.{mix['kind']}")
    res = runner.run(ctx)
    line = harness.result_line(ROOT, spec, cell, ctx, res)
    for k in ("setup_phases", "window_span_s", "slowest_decode_calls",
              "window_compiles", "check_s", "requests_done"):
        if k in res:
            print(f"bench: {k} {res[k]}", file=sys.stderr)
    for k, v in res["checks"].items():
        if k not in line["checks"]:
            print(f"bench: {k} {v}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
