"""What every cell shares: finding files by name, the compile cache, the
trace of the window, and the result line."""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import shutil
import tempfile
from contextlib import contextmanager
from pathlib import Path

__all__ = ["Context", "load_spec", "find_cell", "load_config",
           "load_traffic", "peak", "use_compile_cache", "read_metric",
           "judge", "result_line"]


def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload named {name!r} in BENCHMARK.json")


def load_config(root: Path, spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise SystemExit(f"bench: no configuration named {name!r}")


def load_traffic(root: Path, name: str) -> dict:
    """The mix in ``bench/traffic/<name>.json``."""
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def peak(root: Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache in ``<checkout>/.jax_cache``, every
    program in it, so that a second run of a cell compiles nothing."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_COMPILES = [0]


def count_compiles() -> list[int]:
    """A one-element list that counts, from now on, the programs XLA
    compiles and those it loads from the persistent compile cache."""
    from jax._src import monitoring

    def add(n):
        _COMPILES[0] += n

    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: add("backend_compile" in event))
    monitoring.register_event_listener(
        lambda event, **kw: add(event.endswith("/cache_hits")))
    return _COMPILES


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    peak: dict
    devices: list
    trace_summary: dict | None = None
    compiles: list = dataclasses.field(default_factory=lambda: [0])

    @contextmanager
    def tracing(self):
        """The profiler over the window when ``trace`` is on."""
        if not self.trace:
            yield
            return
        import jax

        from bench.trace import load_events, reduce_trace

        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        # Host events of the runtime and the benchmark's annotations only:
        # tracing every Python call would slow the engine's host loop.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            with jax.profiler.trace(tmp, profiler_options=opts):
                yield
            (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
            self.trace_summary = reduce_trace(load_events(path))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def read_metric(root: Path, name: str, obs: dict):
    """``bench/metrics/<name>.py``'s ``read(obs)``: a number or None."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def judge(config: dict, readings: dict) -> tuple[bool, dict]:
    """``correct``, and each compared number beside its limit: every
    number the configuration's ``check`` gives a limit is compared."""
    limits = config.get("check", {})
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in readings.items() if k in limits}
    correct = bool(checks) and all(
        c["value"] is not None and c["limit"] is not None
        and c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def result_line(root: Path, spec: dict, cell: dict, ctx: Context,
                res: dict) -> dict:
    name = cell["name"]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    metrics = {}
    if not ctx.trace:
        for m in e2e:
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    else:
        obs = dict(res["obs"], trace=ctx.trace_summary)
        for m in spec["per_layer"]:
            if name in m["workloads"]:
                v = read_metric(root, m["name"], obs)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    correct, checks = judge(ctx.config, res["checks"])
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if ctx.trace and ctx.trace_summary is not None:
        device["busy_s"] = ctx.trace_summary["busy_s"]
        device["window_s"] = ctx.trace_summary["window_s"]
        line["breakdown"] = ctx.trace_summary["breakdown"]
    line["checks"] = checks
    return line
