"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Modes:
  default    : full-depth compile with layer scan -- proves the sharding is
               coherent and reports memory_analysis() (the "does it fit"
               evidence) plus HLO-parsed collective traffic (while-body trip
               counts resolved).
  --analysis : roofline mode.  Lowers python-unrolled reduced-depth variants
               at (prefix + period) and (prefix + 2*period) layers and
               extrapolates cost(L) = a + b*L to full depth -- exact for the
               homogeneous layer stack and immune to XLA's count-while-once
               behaviour.  Reports the three roofline terms (SRoofline).

Examples:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-v3-671b \
      --shape train_4k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out <dir>
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

# MUST run before any jax device initialization (the brief's two-line rule;
# kept here at top-of-module before the jax import below).
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, SHAPES, get_config
from repro.configs.base import layer_kinds
from repro.launch.mesh import make_production_mesh, pctx_for_mesh
from repro.launch.specs import build_cell, supported_shapes
from repro.roofline import V5E, model_flops, roofline_from_compiled
from repro.roofline.analysis import parse_hlo_collectives


def _period(cfg):
    p = 1
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.layer_period)
    if cfg.ssm is not None and cfg.ssm.attn_period:
        p = math.lcm(p, cfg.ssm.attn_period)
    pre = cfg.moe.first_dense_layers if cfg.moe is not None else 0
    return pre, p


def _lower_compile(cell, mesh):
    with jax.set_mesh(mesh):          # in_shardings are bare PartitionSpecs
        t0 = time.time()
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.arg_shapes)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return lowered, compiled, t_lower, t_compile


def run_cell(arch: str, shape: str, *, multi_pod: bool, balancer: str,
             analysis: bool, microbatches: int = 1,
             rcfg_overrides: dict | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    pctx = pctx_for_mesh(mesh)
    n_chips = mesh.size
    cfg = get_config(arch)
    spec = SHAPES[shape]
    out: dict = {
        "arch": arch, "shape": shape, "mesh": dict(mesh.shape),
        "chips": n_chips, "balancer": balancer, "mode":
        "analysis" if analysis else "dryrun",
    }

    if not analysis:
        cell = build_cell(arch, shape, pctx, balancer_mode=balancer,
                          microbatches=microbatches,
                          rcfg_overrides=rcfg_overrides)
        lowered, compiled, t_lower, t_compile = _lower_compile(cell, mesh)
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        by_kind, counts, warn = parse_hlo_collectives(hlo)
        out.update({
            "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "generated_code_bytes": ma.generated_code_size_in_bytes,
                "total_bytes": (ma.argument_size_in_bytes
                                + ma.temp_size_in_bytes),
                "hbm_fraction": round(
                    (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
                    / V5E.hbm_bytes, 3),
            },
            "cost_analysis_flops_scan_undercounted": ca.get("flops"),
            "collective_bytes_by_kind": by_kind,
            "collective_counts": counts,
            "warnings": warn,
        })
        return out

    # --- roofline mode: two-point extrapolation over unrolled depth -------
    pre, p = _period(cfg)
    k_full = (cfg.num_layers - pre) / p
    L1, L2 = pre + p, pre + 2 * p
    points = []
    for L in (L1, L2):
        cell = build_cell(arch, shape, pctx, balancer_mode=balancer,
                          analysis=True, num_layers_override=L,
                          rcfg_overrides=rcfg_overrides)
        lowered, compiled, t_lower, t_compile = _lower_compile(cell, mesh)
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        by_kind, counts, warn = parse_hlo_collectives(hlo)
        points.append({
            "L": L,
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": {k: v for k, v in by_kind.items()},
            "coll_total": float(sum(by_kind.values())),
            "warnings": warn,
        })
    c1, c2 = points

    def extrap(a, b):
        return a + (b - a) * (k_full - 1.0)

    flops = extrap(c1["flops"], c2["flops"])
    byts = extrap(c1["bytes"], c2["bytes"])
    coll = extrap(c1["coll_total"], c2["coll_total"])
    coll_by = {k: extrap(c1["coll"].get(k, 0), c2["coll"].get(k, 0))
               for k in set(c1["coll"]) | set(c2["coll"])}

    compute_s = flops / V5E.peak_flops
    memory_s = byts / V5E.hbm_bw
    collective_s = coll / V5E.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, SHAPES[shape], backward=SHAPES[shape].kind == "train")
    mf_per_dev = mf / n_chips
    out.update({
        "points": points,
        "k_full": k_full,
        "flops_per_device": flops,
        "bytes_per_device": byts,
        "collective_bytes_per_device": coll,
        "collective_by_kind": coll_by,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops_per_device": mf_per_dev,
        "useful_flops_ratio": (mf_per_dev / flops) if flops else None,
        "roofline_fraction": compute_s / max(terms.values())
        if max(terms.values()) > 0 else None,
    })
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--balancer", default="ultraep",
                    choices=["none", "eplb", "eplb_plus", "ultraep", "ideal"])
    ap.add_argument("--analysis", action="store_true",
                    help="roofline mode (reduced-depth unrolled extrapolation)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--all", action="store_true",
                    help="iterate every supported (arch x shape) cell")
    ap.add_argument("--include-paper-archs", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    ap.add_argument("--rcfg", default=None,
                    help="JSON dict of RuntimeConfig overrides")
    args = ap.parse_args(argv)

    archs = ASSIGNED_ARCHS + (PAPER_ARCHS if args.include_paper_archs else [])
    if args.list:
        for a in archs:
            cfg = get_config(a)
            print(f"{a:22s} shapes: {', '.join(supported_shapes(cfg))}"
                  + (f"   skips: {', '.join(cfg.shape_skips)}"
                     if cfg.shape_skips else ""))
        return 0

    cells = []
    if args.all:
        for a in archs:
            for s in supported_shapes(get_config(a)):
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        cells = [(args.arch, args.shape)]

    overrides = json.loads(args.rcfg) if args.rcfg else None
    failures = 0
    for arch, shape in cells:
        tag = (f"{arch}|{shape}|{'2pod' if args.multi_pod else '1pod'}"
               f"|{args.balancer}|{'roofline' if args.analysis else 'dryrun'}")
        try:
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           balancer=args.balancer, analysis=args.analysis,
                           microbatches=args.microbatches,
                           rcfg_overrides=overrides)
            res["ok"] = True
            print(f"[OK] {tag}", flush=True)
        except Exception as e:  # noqa: BLE001 -- report and continue
            failures += 1
            res = {"arch": arch, "shape": shape, "ok": False,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()}
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            mode = "roofline" if args.analysis else "dryrun"
            pod = "2pod" if args.multi_pod else "1pod"
            fn = f"{arch}_{shape}_{pod}_{args.balancer}_{mode}.json"
            with open(os.path.join(args.out, fn), "w") as f:
                json.dump(res, f, indent=2, default=str)
        else:
            print(json.dumps(res, indent=2, default=str))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
