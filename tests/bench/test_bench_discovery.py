"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files; the harness finds them by name, and no file the
benchmark already has is edited."""

import hashlib
import json
import shutil
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())

    # The new files.
    base = json.loads((ROOT / "bench/configs/glm-4.5-air.json").read_text())
    (tmp_path / "bench/configs/new-model.json").write_text(
        json.dumps(dict(base, name="new-model", num_hidden_layers=3)))
    mix = json.loads(
        (ROOT / "bench/traffic/serve.prefill_heavy.json").read_text())
    mix.update(arrivals={"process": "poisson", "rate_rps": 0.5},
               output_len={"dist": "uniform", "min": 64, "max": 128})
    (tmp_path / "bench/traffic/serve.new_mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(obs):\n    return 2.0 * obs['x']\n")
    # ... and their entries in BENCHMARK.json.
    spec["configs"].append({"name": "new-model",
                            "file": "bench/configs/new-model.json"})
    cell = {"name": "new-model.serve.new_mix", "config": "new-model",
            "traffic": "serve.new_mix", "chips": 1}
    spec["workloads"].append(cell)
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "layer": "serving engine",
                              "moves": "ttft_p90_ms",
                              "workloads": [cell["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = harness.load_spec(tmp_path)
    found = harness.find_cell(spec, "new-model.serve.new_mix")
    cfg = harness.load_config(tmp_path, spec, found["config"])
    assert cfg["num_hidden_layers"] == 3
    mix = harness.load_traffic(tmp_path, found["traffic"])
    assert mix["arrivals"] == {"process": "poisson", "rate_rps": 0.5}
    assert mix["output_len"]["max"] == 128
    assert mix["prompt_len"]["median"] == 1024
    assert mix["kind"] == "serve"

    ctx = harness.Context(cell=found, config=dict(cfg, check={"gap": 1.0}),
                          traffic=mix, seed=1, seconds=1.0, trace=True,
                          t_start=0.0, peak={}, devices=[_Dev()])
    res = {"e2e": {}, "obs": {"x": 21.0}, "attempted": 1, "failed": 0,
           "memory_peak_bytes": 1, "checks": {"gap": 0.5}}
    line = harness.result_line(tmp_path, spec, found, ctx, res)
    assert line["metrics"] == {"new_metric": {"value": 42.0, "unit": "ms"}}
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert _digests(tmp_path).items() >= before.items()
