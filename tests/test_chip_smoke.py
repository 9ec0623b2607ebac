"""chip_smoke.py refuses to run, and prints no result, without a TPU; the
entry points' compile cache lands where it should."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def test_chip_smoke_fails_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    lines = proc.stdout.strip().splitlines()
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            last = None
        assert not isinstance(last, dict)


_CACHE_SNIPPET = """
import jax
from repro.launch.cache import use_compile_cache
where = use_compile_cache()
print(where)
print(jax.config.jax_compilation_cache_dir)
if {compile_one}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()
"""


def _cache_run(env_dir, compile_one):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_SNIPPET.format(compile_one=compile_one)],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-2:]


def test_compile_cache_in_env_dir_else_checkout(tmp_path):
    where, cfg_dir = _cache_run(tmp_path, compile_one=True)
    assert where == cfg_dir == str(tmp_path)
    assert any(tmp_path.iterdir())          # the compiled program landed

    # Nothing compiles here: the checkout's cache is only named, not filled.
    where, cfg_dir = _cache_run(None, compile_one=False)
    assert where == cfg_dir == str(ROOT / ".jax_cache")


_STEP_CACHE_SNIPPET = """
import jax
from repro.launch.cache import use_compile_cache
from repro.launch.train import compile_step, train
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
kw = dict(reduce=True, num_layers=1, steps=2, batch=2, seq=16)
print("compiled", compile_step("qwen3-235b-a22b", balancer="none", **kw))
assert not hits
r = train("qwen3-235b-a22b", balancer="none", log_every=1, **kw)
assert r.restarts == 0 and len(r.losses) == 2, r
print("HITS", len(hits))
"""


def test_compile_step_is_the_program_train_loads(tmp_path):
    """compile_step compiles exactly the step train() runs: train() then
    loads it from the persistent cache instead of compiling again."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", _STEP_CACHE_SNIPPET],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr
    assert "HITS 1" in proc.stdout, proc.stdout
