"""Put the device's time and idle gaps down to the program's own names.

    python bench/scopes.py --workload <name> --seed <n> --seconds <s>

runs one cell once through ``bench/run.py --trace 1`` and prints its
result line; on standard error it adds the per-layer metrics below
(``scopes: metrics``), the device seconds of every program scope, the
idle gaps named by the innermost program span, and the runtime's host
events over the longest chip-idle stretches inside decode calls.

The program marks its work three ways.  Device ops carry the
``jax.named_scope`` path they were traced under (``attn``, ``moe.ffn``,
...) in the compiled program's metadata, which the profiler's
``*.trace.json.gz`` gives as each op's ``tf_op``; host spans
``uep.<what>`` (``repro.tracing``) lie on the profiler's clock beside the
device's ops.  :func:`load_profile` reads both with the runtime's own host
events of ``RUNTIME_MIN_S`` or more, as ``bench.trace.Event`` tuples.

Device time per scope counts leaf ops only: on a TPU a ``while`` op's
event spans the events of its body's ops, which are counted instead.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict
from contextlib import contextmanager
from pathlib import Path
from typing import NamedTuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # The TPU runtime would otherwise log to a fixed directory under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for _p in (str(ROOT), str(ROOT / "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import harness                                  # noqa: E402
from bench.trace import (MODULES_LINE, OPS_LINE, SPAN_PREFIX,  # noqa: E402
                         Event, busy_in, device_planes, load_events,
                         reduce_trace, union)

__all__ = ["SCOPES", "UEP", "Profile", "load_profile", "op_paths",
           "scope_of", "leaf_ops", "scoped_ops", "scope_seconds",
           "program_runs", "named_idle", "idle_inside", "host_events_over",
           "reduce_scopes", "metrics", "report", "PER_RUN", "UNITS",
           "ScopedContext"]

# The program's named scopes, as repro's models and MoE stages open them.
SCOPES = ("embed", "attn", "ffn.dense", "moe.gate", "moe.plan",
          "moe.distribute", "moe.dispatch", "moe.ffn", "moe.combine",
          "moe.shared", "head")
UEP = "uep."
RUNTIME_MIN_S = 1e-3
NO_SCOPE = "no scope"
NO_SPAN = "no span"


class Profile(NamedTuple):
    events: list[Event]       # device ops and programs, host spans, runtime
    paths: dict               # (program run, HLO op) -> name-stack path


def _program(module_name: str) -> str:
    """``jit__prefill(123)`` -> ``jit__prefill``."""
    return module_name.split("(")[0]


def _op_name(long_name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    m = re.match(r"%?([\w.\-]+)\s*=", long_name)
    return m.group(1) if m else long_name


def op_paths(trace_json: str) -> dict:
    """(program run's name, HLO op) -> the op's name-stack path, from a
    profiler's ``*.trace.json.gz``: each device op's ``tf_op``, its program
    the ``XLA Modules`` event over it.  The file may stop short of the
    trace's end; every op of a program is named by that program's first
    run in it."""
    with gzip.open(trace_json, "rt") as f:
        events = json.load(f)["traceEvents"]
    lines = {(e["pid"], e.get("tid")): e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    mods: dict = defaultdict(list)
    ops = []
    for e in events:
        line = lines.get((e.get("pid"), e.get("tid")))
        if e.get("ph") != "X" or line not in (OPS_LINE, MODULES_LINE):
            continue
        if line == MODULES_LINE:
            mods[e["pid"]].append((e["ts"], e["ts"] + e["dur"], e["name"]))
        elif "tf_op" in (e.get("args") or {}):
            ops.append(e)
    for m in mods.values():
        m.sort()
    out = {}
    for e in ops:
        m = mods[e["pid"]]
        j = bisect.bisect_right(m, (e["ts"], float("inf"))) - 1
        if j >= 0 and m[j][1] >= e["ts"]:
            a = e["args"]
            out.setdefault((m[j][2], _op_name(a.get("long_name", e["name"]))),
                           a["tf_op"].split(":")[0])
    return out


def load_profile(profile_dir: str) -> Profile:
    """Device ops and programs, ``bench.``/``uep.`` host spans and the
    runtime's host events of ``RUNTIME_MIN_S`` or more, with each op's
    name-stack path, from the profiler's output under ``profile_dir``."""
    from jax.profiler import ProfileData

    (xplane,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        dev = plane.name.startswith("/device:")
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if dev or ev.name.startswith((SPAN_PREFIX, UEP)) \
                        or d >= RUNTIME_MIN_S:
                    out.append(Event(plane.name, line.name, ev.name, s,
                                     s + d))
    paths = {}
    for tj in glob.glob(f"{profile_dir}/**/*.trace.json.gz", recursive=True):
        paths.update(op_paths(tj))
    return Profile(out, paths)


def scope_of(path: str) -> str:
    """The innermost program scope on a name-stack path, or NO_SCOPE."""
    hits = [p for p in path.split("/") if p in SCOPES]
    return hits[-1] if hits else NO_SCOPE


def leaf_ops(ops: list[Event]) -> list[Event]:
    """The ops of one device whose interval holds no other op's: a
    ``while`` whose body ops appear as events of their own goes."""
    ops = sorted(ops, key=lambda e: (e.start, -e.end))
    parent = [False] * len(ops)
    stack: list[int] = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= ops[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(ops, parent) if not p]


def scoped_ops(profile: Profile, plane: str, lo: float, hi: float
               ) -> list[tuple[str, str, float, float]]:
    """(program, scope, start, end) of each leaf op on ``plane``, clipped
    to the window."""
    ev = profile.events
    mods = sorted((e.start, e.end, e.name) for e in ev
                  if e.plane == plane and e.line == MODULES_LINE)
    starts = [m[0] for m in mods]
    out = []
    for e in leaf_ops([e for e in ev
                       if e.plane == plane and e.line == OPS_LINE]):
        if e.end <= lo or e.start >= hi:
            continue
        j = bisect.bisect_right(starts, e.start) - 1
        mod = mods[j][2] if j >= 0 and mods[j][1] >= e.start else "?"
        path = profile.paths.get((mod, _op_name(e.name)), "")
        out.append((_program(mod), scope_of(path), max(e.start, lo),
                     min(e.end, hi)))
    return out


def scope_seconds(ops) -> dict:
    """{program: {scope: device seconds}} over ``scoped_ops``."""
    tot: dict = defaultdict(lambda: defaultdict(float))
    for prog, scope, s, e in ops:
        tot[prog][scope] += e - s
    return {p: dict(v) for p, v in tot.items()}


def program_runs(events: list[Event], plane: str, lo: float, hi: float
                 ) -> dict:
    """{program: runs that start in the window} on ``plane``."""
    runs: dict = defaultdict(int)
    for e in events:
        if e.plane == plane and e.line == MODULES_LINE and lo <= e.start < hi:
            runs[_program(e.name)] += 1
    return dict(runs)


def _uep_spans(events: list[Event]) -> list[Event]:
    return [e for e in events if e.name.startswith(UEP)
            and not e.plane.startswith("/device:")]


def _idle(events: list[Event], plane: str, lo: float, hi: float):
    busy = busy_in(events, plane, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def named_idle(events: list[Event], plane: str, lo: float, hi: float
               ) -> dict:
    """The device's idle seconds in the window, summed by the innermost
    ``uep.`` span open over them (NO_SPAN where none is)."""
    marks = []
    for i, s in enumerate(_uep_spans(events)):
        marks += [(s.start, 1, -(s.end - s.start), i, s.name),
                  (s.end, 0, 0.0, i, s.name)]
    marks.sort()
    # The host timeline cut where a span opens or closes: piece starts
    # and the innermost span over each piece.
    cuts, names, stack = [lo], [NO_SPAN], []
    for t, opens, _, i, name in marks:
        if opens:
            stack.append((i, name))
        elif (i, name) in stack:
            stack.remove((i, name))
        cuts.append(t)
        names.append(stack[-1][1] if stack else NO_SPAN)
    cuts.append(float("inf"))
    tot: dict = defaultdict(float)
    for a, b in _idle(events, plane, lo, hi):
        k = max(bisect.bisect_right(cuts, a) - 1, 0)
        while k < len(names) and cuts[k] < b:
            x, y = max(a, cuts[k]), min(b, cuts[k + 1])
            if y > x:
                tot[names[k]] += y - x
            k += 1
    return dict(tot)


def idle_inside(events: list[Event], plane: str, name: str, lo: float,
                hi: float) -> list[tuple[float, float]]:
    """The device's idle stretches in the window that lie inside host
    spans called ``name``, longest first."""
    spans = union((s.start, s.end) for s in _uep_spans(events)
                  if s.name == name)
    out = []
    for a, b in _idle(events, plane, lo, hi):
        for s, e in spans:
            x, y = max(a, s), min(b, e)
            if y > x:
                out.append((x, y))
    return sorted(out, key=lambda iv: iv[0] - iv[1])


def host_events_over(events: list[Event], a: float, b: float) -> list:
    """The runtime's host events (neither ``bench.`` nor ``uep.`` spans)
    that overlap [a, b]: [[thread, name, start - a, seconds], ...]."""
    return [[e.line, e.name[:120], e.start - a, e.end - e.start]
            for e in sorted(events, key=lambda e: e.start)
            if not e.plane.startswith("/device:")
            and not e.name.startswith((SPAN_PREFIX, UEP))
            and e.start < b and e.end > a]


def reduce_scopes(profile: Profile) -> dict:
    """Device seconds and runs by program and scope, idle named by the
    program's spans, and the idle stretches inside decode calls, over the
    ``bench.window`` span on the busiest device."""
    ev = profile.events
    win = [e for e in ev if e.name == SPAN_PREFIX + "window"
           and not e.plane.startswith("/device:")]
    if not win:
        raise ValueError("trace has no bench.window span")
    lo, hi = win[0].start, win[0].end
    planes = device_planes(ev)
    if not planes:
        raise ValueError("trace has no device ops")
    plane = max(planes, key=lambda p: sum(
        b - a for a, b in busy_in(ev, p, lo, hi)))
    stalls = idle_inside(ev, plane, UEP + "engine.decode", lo, hi)
    return {
        "window_s": hi - lo,
        "scope_s": scope_seconds(scoped_ops(profile, plane, lo, hi)),
        "runs": program_runs(ev, plane, lo, hi),
        "named_idle_s": named_idle(ev, plane, lo, hi),
        "decode_idle_s": sum(b - a for a, b in stalls),
        "decode_stalls": [[a - lo, b - a, host_events_over(ev, a, b)]
                          for a, b in stalls[:3]],
    }


# Per-layer metrics read from a scope's device time per program run:
# name -> (program, scope).
PER_RUN = {"attn_ms.prefill": ("jit__prefill", "attn"),
           "attn_ms.decode": ("jit__decode", "attn"),
           "distribute_ms.prefill": ("jit__prefill", "moe.distribute"),
           "distribute_ms.decode": ("jit__decode", "moe.distribute"),
           "expert_ffn_ms.prefill": ("jit__prefill", "moe.ffn")}
UNITS = dict.fromkeys(PER_RUN, "ms") | {"idle_engine_share.serve": "%",
                                        "decode_idle_ms.serve": "ms"}


def metrics(summary: dict) -> dict:
    """The per-layer metrics this reduction reads, by name; a metric whose
    program, scope or spans the trace lacks is left out."""
    out = {}
    for name, (prog, scope) in PER_RUN.items():
        n = summary["runs"].get(prog, 0)
        s = summary["scope_s"].get(prog, {}).get(scope)
        if n and s is not None:
            out[name] = 1e3 * s / n
    idle = summary["named_idle_s"]
    if any(k.startswith(UEP) for k in idle):
        out["idle_engine_share.serve"] = 100.0 * sum(
            v for k, v in idle.items()
            if k.startswith(UEP + "engine.")) / summary["window_s"]
        out["decode_idle_ms.serve"] = 1e3 * summary["decode_idle_s"]
    return out


def report(summary: dict, file) -> None:
    """The scope seconds, the named idle gaps and the decode stalls."""
    for prog, by in sorted(summary["scope_s"].items()):
        tot = sum(by.values())
        runs = summary["runs"].get(prog, 0)
        print(f"scopes: {prog} runs {runs} device_s {tot:.4f} "
              f"unscoped_share {by.get(NO_SCOPE, 0.0) / tot:.4f}", file=file)
        for scope, s in sorted(by.items(), key=lambda kv: -kv[1]):
            print(f"scopes:   {scope} {s:.4f} s "
                  f"{1e3 * s / max(runs, 1):.3f} ms/run", file=file)
    for k, v in sorted(summary["named_idle_s"].items(),
                       key=lambda kv: -kv[1]):
        print(f"idle: {k} {v:.4f} s", file=file)
    for at, dur, host in summary["decode_stalls"]:
        print(f"decode idle stretch at {at:.3f} s: {dur:.4f} s", file=file)
        for thread, name, t, d in sorted(host, key=lambda h: -h[3])[:12]:
            print(f"    {thread} {name} +{t:.4f} s {d:.4f} s", file=file)


class ScopedContext(harness.Context):
    """The harness's traced window, reduced by the program's names too:
    ``scopes`` holds :func:`reduce_scopes` of the window's profile.  The
    latest context to trace is ``ScopedContext.last``."""

    scopes = None
    last = None

    @contextmanager
    def tracing(self):
        import jax

        ScopedContext.last = self
        tmp = tempfile.mkdtemp(prefix="bench-scopes-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            with jax.profiler.trace(tmp, profiler_options=opts):
                yield
            (xp,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
            self.trace_summary = reduce_trace(load_events(xp))
            self.scopes = reduce_scopes(load_profile(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    """``bench/run.py --trace 1`` with :class:`ScopedContext` in place of
    the harness's context; then the reduction's report and metrics on
    standard error."""
    from bench import run

    harness.Context = ScopedContext
    args = sys.argv[1:] if argv is None else list(argv)
    rc = run.main([*args, "--trace", "1"])
    ctx = ScopedContext.last
    if rc or ctx is None or ctx.scopes is None:
        return rc
    report(ctx.scopes, sys.stderr)
    print("scopes: metrics " + json.dumps(
        {k: {"value": v, "unit": UNITS[k]}
         for k, v in metrics(ctx.scopes).items()}), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
