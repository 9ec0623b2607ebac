"""Repo-specific JAX lint: an AST pass over ``src/`` (DESIGN.md S10).

Rules (all severity "error"; suppress per line with a trailing
``# uep-lint: disable=<rule>[,<rule>...]`` comment, or skip a whole file
with ``# uep-lint: skip-file`` in its first ten lines):

* ``axis-name``       -- a string literal passed as the axis name of a
                         ``jax.lax`` collective must be one of the canonical
                         mesh axis names (``data``/``model``/``pod``/``rack``,
                         the :class:`repro.models.transformer.ParallelCtx` /
                         :class:`repro.parallel.sharding.MeshAxes`
                         vocabulary).  Axis-name drift between the mesh
                         builder and a collective produces either a trace
                         error far from the typo or, worse, a reduction over
                         the wrong axis.
* ``host-sync``       -- no ``.item()`` / ``np.asarray`` / ``np.array`` /
                         ``float()``/``int()`` on traced values inside
                         functions that build jitted computations: each one
                         is a device->host sync that either crashes under
                         ``jit`` or silently serialises the hot path.
* ``float64-literal`` -- no float64 dtypes in ``kernels/`` or ``moe/`` code:
                         TPUs have no f64 ALU, so a stray literal means
                         silent x64-disabled truncation or a huge emulation
                         penalty.
* ``rack-loop``       -- no Python ``for`` loop over ``*.racks`` inside a
                         traced function: under ``shard_map`` the loop
                         unrolls per rack into the graph, breaking the
                         topology-transparency contract (use vectorised
                         rack-major reshapes as in ``two_hop_all_to_all``).
* ``stage-boundary``  -- the MoE dispatch/permute/distribute engine
                         primitives (``fused_dispatch``, ``fused_bucket``,
                         ``materialize_replica_stack``, ...) may only be called
                         from the staged execution layer
                         (``repro.moe.stages``) and the engine modules
                         themselves.  Everything else must go through the
                         typed stage outputs of :mod:`repro.moe.stages`
                         (DESIGN.md S11) -- ad-hoc cross-stage plumbing is
                         how the pre-refactor layer monolith grew.
* ``wire-dtype``      -- no ``.astype(int8 | bfloat16)`` on buffers inside
                         the ``moe/`` engine modules: wire-dtype conversion
                         belongs exclusively to the
                         :mod:`repro.core.quantize` codec helpers
                         (``encode_wire``/``decode_wire``/``encode_int8``).
                         An ad-hoc cast next to an already-encoded payload
                         silently double-quantizes (or strips the in-band
                         scales) and no test that compares at tolerance
                         will catch the extra half-step of error
                         (DESIGN.md S12).
* ``rack-limit``      -- no ``top_k`` calls (``jax.lax.top_k`` /
                         ``jnp.top_k``) in MoE engine modules outside
                         ``repro.moe.gating``: expert selection must go
                         through the gate so the rack-group mask of
                         rack-limited routing (DESIGN.md S14) is applied.
                         An ad-hoc top-k over expert scores elsewhere
                         silently bypasses the ``rack_limit`` bound and
                         re-inflates inter-rack traffic.
* ``fallback-path``   -- no bare ``except:`` and no ``except Exception:`` /
                         ``except BaseException:`` whose body only ``pass``es
                         in ``repro`` code: the degradation ladder
                         (DESIGN.md S13) depends on failures being *counted
                         and degraded*, never silently swallowed -- a
                         swallow-all handler turns an injected fault test
                         into a false pass.  Handlers that actually do
                         something (log, count, fall back) are fine.

Functions are considered *traced* when their bodies reference ``jnp`` /
``jax.lax`` / ``jax.nn`` -- a deliberate over-approximation: host-side numpy
modules (``comm_plan``, ``ref_planner``, ``eplb``'s numpy half) contain no
such references and are never flagged, while everything that can end up
inside ``jit``/``shard_map`` is.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Iterable

__all__ = ["LintViolation", "RULES", "lint_source", "lint_file",
           "lint_paths", "main"]


@dataclasses.dataclass(frozen=True)
class LintViolation:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: " \
               f"{self.message}"


RULES = ("axis-name", "host-sync", "float64-literal", "rack-loop",
         "stage-boundary", "wire-dtype", "rack-limit", "fallback-path")

# Canonical mesh-axis vocabulary: ParallelCtx defaults (batch_axes=("data",),
# model_axis="model") plus the documented factored/mesh extras ("pod" FSDP
# axis, "rack" scale-out EP axis).  Keep in sync with
# repro.models.transformer.ParallelCtx and repro.parallel.sharding.MeshAxes.
ALLOWED_AXIS_NAMES = frozenset({"data", "model", "pod", "rack"})

# jax.lax collectives -> positional index of their axis-name argument.
_COLLECTIVE_AXIS_ARG = {
    "all_to_all": 1,
    "all_gather": 1,
    "all_gather_invariant": 1,
    "psum": 1,
    "pmean": 1,
    "pmax": 1,
    "pmin": 1,
    "ppermute": 1,
    "pshuffle": 1,
    "psum_scatter": 1,
    "axis_index": 0,
    "axis_size": 0,
}
_AXIS_KEYWORDS = ("axis_name", "axis")

_SUPPRESS_RE = re.compile(r"#\s*uep-lint:\s*disable=([\w,\- ]+)")
_SKIP_FILE_RE = re.compile(r"#\s*uep-lint:\s*skip-file")

# float64-literal applies only where kernel/moe code lives.
_F64_PATH_PARTS = ("kernels", "moe")

# wire-dtype applies to the MoE engine modules (payload buffers live there);
# repro.core.quantize is outside this scope by construction, so the codec
# helpers themselves are exempt.
_WIRE_PATH_PARTS = ("moe",)
_WIRE_DTYPES_FLAGGED = ("int8", "bfloat16")

# rack-limit: expert selection is confined to the gate (repro.moe.gating),
# the single module that applies the rack-group mask.  A top_k anywhere else
# under moe/ is selection that bypasses the mask.
_RACK_LIMIT_PATH_PARTS = ("moe",)
_RACK_LIMIT_EXEMPT_STEMS = frozenset({"gating"})
_TOP_K_PREFIXES = ("jax.lax", "lax", "jnp", "jax.numpy")

# fallback-path applies to library code under repro/ (tests and tools may
# legitimately probe with broad handlers).
_FALLBACK_PATH_PARTS = ("repro",)

# stage-boundary: engine primitives whose call sites are confined to the
# staged execution layer and the engine modules themselves.  Keep in sync
# with repro.moe.stages (DESIGN.md S11).
_STAGE_PRIMS = frozenset({
    "fused_dispatch", "fused_bucket", "fused_unbucket", "fused_combine",
    "fused_replicated_bucket", "fused_replicated_combine",
    "two_hop_all_to_all", "materialize_replica_stack",
    "dispatch_tokens", "bucket_by_slot", "unbucket", "combine_tokens",
})
# moe/ module stems allowed to call them: the stage driver plus the modules
# that define (and internally compose) the primitives.
_STAGE_EXEMPT_STEMS = frozenset(
    {"stages", "permute", "distribute", "dispatch", "expert"})


def _stage_exempt(path: str) -> bool:
    parts = Path(path).parts
    return (len(parts) >= 2 and parts[-2] == "moe"
            and Path(path).stem in _STAGE_EXEMPT_STEMS)


def _dotted(node: ast.AST) -> str:
    """'jax.lax.psum' for an Attribute/Name chain, '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _uses_jax(node: ast.AST) -> bool:
    """True when the subtree references jnp / jax.lax / jax.nn."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in ("jnp", "lax"):
            return True
        if isinstance(sub, ast.Attribute):
            d = _dotted(sub)
            if d.startswith(("jax.lax", "jax.nn", "jax.numpy", "jnp.")):
                return True
    return False


def _contains_jax_call(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d.startswith(("jnp.", "jax.lax.", "jax.nn.", "lax.")):
                return True
    return False


def _traced_names(fn: ast.AST) -> set[str]:
    """Local names assigned from expressions containing a jnp/jax call."""
    names: set[str] = set()
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Assign) and _contains_jax_call(sub.value):
            for tgt in sub.targets:
                for t in ast.walk(tgt):
                    if isinstance(t, ast.Name):
                        names.add(t.id)
        elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
            if sub.value is not None and _contains_jax_call(sub.value) \
                    and isinstance(sub.target, ast.Name):
                names.add(sub.target.id)
    return names


def _axis_literals(call: ast.Call) -> Iterable[ast.Constant]:
    """String-literal axis names passed to a jax.lax collective call."""
    fn = _dotted(call.func)
    attr = fn.rsplit(".", 1)[-1]
    if attr not in _COLLECTIVE_AXIS_ARG:
        return
    if not (fn.startswith("jax.lax.") or fn.startswith("lax.")):
        return
    cands: list[ast.expr] = []
    pos = _COLLECTIVE_AXIS_ARG[attr]
    if len(call.args) > pos:
        cands.append(call.args[pos])
    for kw in call.keywords:
        if kw.arg in _AXIS_KEYWORDS:
            cands.append(kw.value)
    for c in cands:
        if isinstance(c, ast.Constant) and isinstance(c.value, str):
            yield c
        elif isinstance(c, (ast.Tuple, ast.List)):
            for el in c.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    yield el


def _is_f64(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "float64":
        return _dotted(node).split(".")[0] in ("np", "numpy", "jnp", "jax")
    return (isinstance(node, ast.Constant) and node.value == "float64")


def _wire_dtype_cast(call: ast.Call) -> str | None:
    """The flagged dtype name when ``call`` is ``.astype(int8|bfloat16)``."""
    if not (isinstance(call.func, ast.Attribute)
            and call.func.attr == "astype" and call.args):
        return None
    a = call.args[0]
    if isinstance(a, ast.Attribute) and a.attr in _WIRE_DTYPES_FLAGGED \
            and _dotted(a).split(".")[0] in ("np", "numpy", "jnp", "jax"):
        return a.attr
    if isinstance(a, ast.Constant) and a.value in _WIRE_DTYPES_FLAGGED:
        return str(a.value)
    return None


def _swallows_all(handler: ast.ExceptHandler) -> str | None:
    """Why an except handler is a silent swallow-all, or None if it isn't."""
    if handler.type is None:
        return "bare except:"
    names = []
    types = handler.type.elts if isinstance(handler.type,
                                            (ast.Tuple, ast.List)) \
        else [handler.type]
    for t in types:
        d = _dotted(t)
        names.append(d.rsplit(".", 1)[-1] if d else "")
    if not any(n in ("Exception", "BaseException") for n in names):
        return None
    if all(isinstance(s, ast.Pass) for s in handler.body):
        return f"except {'/'.join(filter(None, names))}: pass"
    return None


class _FileLinter:
    def __init__(self, path: str, tree: ast.Module, check_f64: bool,
                 check_wire: bool = False, check_fallback: bool = False,
                 check_rack_limit: bool = False):
        self.path = path
        self.check_f64 = check_f64
        self.check_wire = check_wire
        self.check_fallback = check_fallback
        self.check_rack_limit = (check_rack_limit and
                                 Path(path).stem not in
                                 _RACK_LIMIT_EXEMPT_STEMS)
        self.check_stage = not _stage_exempt(path)
        self.tree = tree
        self.found: dict[tuple[int, int, str], LintViolation] = {}

    def emit(self, node: ast.AST, rule: str, message: str) -> None:
        key = (node.lineno, node.col_offset, rule)
        self.found.setdefault(
            key, LintViolation(self.path, node.lineno, node.col_offset,
                               rule, message))

    def run(self) -> list[LintViolation]:
        # Module-wide rules (axis names, float64 literals, stage boundary).
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                if self.check_stage:
                    prim = _dotted(node.func).rsplit(".", 1)[-1]
                    if prim in _STAGE_PRIMS:
                        self.emit(
                            node, "stage-boundary",
                            f"{prim}() is a cross-stage engine primitive; "
                            "outside repro.moe.stages go through the typed "
                            "stage outputs (run_staged_moe / the stage "
                            "functions) instead of calling it directly")
                for lit in _axis_literals(node):
                    if lit.value not in ALLOWED_AXIS_NAMES:
                        self.emit(
                            lit, "axis-name",
                            f"axis name {lit.value!r} is not a canonical "
                            f"mesh axis {sorted(ALLOWED_AXIS_NAMES)}; pass "
                            "the ParallelCtx/MeshAxes name instead of a "
                            "fresh literal")
                if self.check_rack_limit:
                    d = _dotted(node.func)
                    if d.endswith(".top_k") and \
                            d.rsplit(".", 1)[0] in _TOP_K_PREFIXES:
                        self.emit(
                            node, "rack-limit",
                            f"{d}() outside repro.moe.gating: top-k expert "
                            "selection must go through gate() so the "
                            "rack-group mask of rack-limited routing "
                            "(GatingConfig.rack_limit, DESIGN.md S14) is "
                            "applied; an ad-hoc top-k bypasses the bound")
                if self.check_wire:
                    dt = _wire_dtype_cast(node)
                    if dt is not None:
                        self.emit(
                            node, "wire-dtype",
                            f".astype({dt}) in a MoE engine module: wire "
                            "dtype conversion belongs to the "
                            "repro.core.quantize codec (encode_wire/"
                            "decode_wire); an ad-hoc cast double-quantizes "
                            "already-encoded payloads")
            if self.check_fallback and isinstance(node, ast.ExceptHandler):
                why = _swallows_all(node)
                if why is not None:
                    self.emit(
                        node, "fallback-path",
                        f"{why} silently swallows failures; the degradation "
                        "ladder (DESIGN.md S13) requires faults to be "
                        "counted and degraded -- catch the specific "
                        "exception, or count/fall back in the handler")
            if self.check_f64 and _is_f64(node):
                self.emit(node, "float64-literal",
                          "float64 in kernel/moe code: TPUs have no f64 "
                          "ALU (use float32 or an explicit tolerance "
                          "policy)")
        # Traced-function rules.
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and _uses_jax(node):
                self._lint_traced_fn(node)
        return sorted(self.found.values(), key=lambda v: (v.line, v.col))

    def _lint_traced_fn(self, fn: ast.AST) -> None:
        traced = _traced_names(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                self._host_sync(node, traced)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                for sub in ast.walk(node.iter):
                    if isinstance(sub, ast.Attribute) and sub.attr == "racks":
                        self.emit(
                            node, "rack-loop",
                            "Python loop over topology racks in a traced "
                            "function unrolls per rack under shard_map; "
                            "use a rack-major reshape + vectorised op")
                        break

    def _host_sync(self, call: ast.Call, traced: set[str]) -> None:
        fn = _dotted(call.func)
        if isinstance(call.func, ast.Attribute) and call.func.attr == "item" \
                and not call.args:
            self.emit(call, "host-sync",
                      ".item() in a traced function is a device->host sync "
                      "(crashes under jit)")
            return
        if fn in ("np.asarray", "np.array", "numpy.asarray", "numpy.array"):
            self.emit(call, "host-sync",
                      f"{fn}() in a traced function forces the value to "
                      "host; use jnp, or move the numpy work out of the "
                      "traced path")
            return
        if isinstance(call.func, ast.Name) and call.func.id in ("float",
                                                                "int") \
                and call.args:
            arg = call.args[0]
            is_traced_name = isinstance(arg, ast.Name) and arg.id in traced
            if is_traced_name or _contains_jax_call(arg):
                self.emit(call, "host-sync",
                          f"{call.func.id}() on a traced value is a "
                          "device->host sync (crashes under jit)")


def _suppressed(lines: list[str], v: LintViolation) -> bool:
    if v.line - 1 >= len(lines):
        return False
    m = _SUPPRESS_RE.search(lines[v.line - 1])
    if not m:
        return False
    rules = {r.strip() for r in m.group(1).split(",")}
    return "all" in rules or v.rule in rules


def lint_source(source: str, path: str = "<string>") -> list[LintViolation]:
    """Lint one source string; returns unsuppressed violations."""
    lines = source.splitlines()
    for ln in lines[:10]:
        if _SKIP_FILE_RE.search(ln):
            return []
    tree = ast.parse(source, filename=path)
    check_f64 = any(part in _F64_PATH_PARTS for part in Path(path).parts)
    check_wire = any(part in _WIRE_PATH_PARTS for part in Path(path).parts)
    check_fb = any(part in _FALLBACK_PATH_PARTS for part in Path(path).parts)
    check_rl = any(part in _RACK_LIMIT_PATH_PARTS
                   for part in Path(path).parts)
    found = _FileLinter(path, tree, check_f64, check_wire, check_fb,
                        check_rl).run()
    return [v for v in found if not _suppressed(lines, v)]


def lint_file(path: str | Path) -> list[LintViolation]:
    p = Path(path)
    return lint_source(p.read_text(), str(p))


def lint_paths(paths: Iterable[str | Path]) -> list[LintViolation]:
    """Lint every ``*.py`` under the given files/directories."""
    out: list[LintViolation] = []
    for root in paths:
        root = Path(root)
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            out.extend(lint_file(f))
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="UltraEP repo lint: repo-specific JAX rules "
                    "(see repro.analysis.lint)")
    ap.add_argument("paths", nargs="*", default=["src"],
                    help="files or directories to lint (default: src)")
    args = ap.parse_args(argv)
    violations = lint_paths(args.paths)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} violation(s)")
        return 1
    print("lint clean")
    return 0
