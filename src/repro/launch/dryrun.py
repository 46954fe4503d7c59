import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST precede any jax import (jax locks the device count on
first init). Run as a program, this module is the only place the 512
placeholder devices exist; importing it (as hillclimb does) leaves the
importer's devices alone, so tests and benches see the real CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import sys
import time
import traceback
from typing import Dict

import jax

from ..configs import ARCH_IDS, get_config
from ..models import build_model
from ..models.config import SHAPES
from ..optim import AdamWConfig
from ..serve import make_prefill_step, make_serve_step
from ..train import make_train_step
from ..train.sharding import make_plan
from .mesh import make_production_mesh
from .roofline import analyze, collective_bytes
from .specs import cell_is_applicable, input_specs


def build_step(cfg, shape):
    model = build_model(cfg)
    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        return make_train_step(model, cfg, opt_cfg)
    if shape.kind == "prefill":
        return make_prefill_step(model, cfg)
    return make_serve_step(model, cfg)


def donate_for(shape) -> tuple:
    """Production buffer donation: train donates (params, opt) — the step
    returns their successors; decode donates the KV cache (in-place
    update). Without donation memory_analysis double-counts these."""
    if shape.kind == "train":
        return (0, 1)
    if shape.kind == "decode":
        return (1,)
    return ()


def depth_variant(cfg, n_layers: int):
    """Same width, reduced depth, layers UNROLLED (a lax.scan body is
    counted once by cost_analysis whatever its trip count, so the variants
    must not scan for the per-layer delta to be observable)."""
    kw = {"n_layers": n_layers, "scan_layers": False,
          # unrolled attention blocks with static skipping: what the flash
          # kernel actually executes, visible to cost_analysis
          "attn_unroll": True,
          "attn_block_q": 2048, "attn_block_kv": 2048}
    if cfg.family == "encdec":
        kw.update(n_enc_layers=n_layers, n_dec_layers=n_layers)
    return cfg.scaled(**kw)


def _cost_tuple(cfg, shape, plan, mesh):
    """(flops, bytes, coll_dict) per device from one lower+compile."""
    from ..train.sharding import use_plan
    step = build_step(cfg, shape)
    args = input_specs(cfg, shape, plan)
    with mesh, use_plan(plan):
        compiled = jax.jit(step, donate_argnums=donate_for(shape)) \
            .lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            collective_bytes(compiled.as_text()))


def extrapolated_cost(cfg, shape, plan, mesh):
    """XLA's cost_analysis counts a lax.scan body ONCE regardless of trip
    count (verified empirically). Compile two reduced-depth variants at
    full width and extrapolate linearly to the real depth:
        cost(L) = cost(L1) + (L - L1) * (cost(L2) - cost(L1)) / (L2 - L1).
    Exact because every scan iteration is the identical program."""
    plen = len(cfg.hybrid_pattern) if cfg.family == "hybrid" else 1
    L = cfg.n_layers
    L1, L2 = plen, 2 * plen
    if L <= L2:  # shallow smoke-scale config: just measure directly
        f, b, c = _cost_tuple(cfg, shape, plan, mesh)
        return f, b, c, False
    f1, b1, c1 = _cost_tuple(depth_variant(cfg, L1), shape, plan, mesh)
    f2, b2, c2 = _cost_tuple(depth_variant(cfg, L2), shape, plan, mesh)
    k = (L - L1) / (L2 - L1)
    f = f1 + (f2 - f1) * k
    b = b1 + (b2 - b1) * k
    coll = {key: int(c1.get(key, 0)
                     + (c2.get(key, 0) - c1.get(key, 0)) * k)
            for key in set(c1) | set(c2)}
    return f, b, coll, True


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        cell["status"] = "skipped"
        cell["reason"] = why
        return cell
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(mesh)
    step = build_step(cfg, shape)
    args = input_specs(cfg, shape, plan)
    from ..train.sharding import use_plan
    with mesh, use_plan(plan):
        lowered = jax.jit(step, donate_argnums=donate_for(shape)).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        roof = analyze(compiled, cfg, shape, n_chips=mesh.size)
    # scan-aware cost correction (see extrapolated_cost)
    f, b, coll, extrap = extrapolated_cost(cfg, shape, plan, mesh)
    roof.flops, roof.hbm_bytes, roof.coll_bytes = f, b, coll
    cell.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "cost_extrapolated": extrap,
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        },
        "roofline": roof.row(),
        "coll_breakdown": roof.coll_bytes,
    })
    if verbose:
        r = roof.row()
        print(f"[{arch} x {shape_name} x {mesh_name}] OK "
              f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
              f"bottleneck={r['bottleneck']} "
              f"t=(c {r['t_compute_s']:.2e}, m {r['t_memory_s']:.2e}, "
              f"x {r['t_collective_s']:.2e}) "
              f"useful={r['useful_ratio']:.2f} "
              f"roofline={r['roofline_fraction']:.2f}", flush=True)
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        targets = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        if not args.arch:
            ap.error("--arch or --all required")
        shapes = [args.shape] if args.shape else list(SHAPES)
        targets = [(args.arch.replace("-", "_"), s) for s in shapes]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch, shape in targets:
        for mp in meshes:
            try:
                cells.append(run_cell(arch, shape, mp))
            except Exception as e:  # record, keep going
                failures += 1
                traceback.print_exc()
                cells.append({"arch": arch, "shape": shape,
                              "mesh": "2x16x16" if mp else "16x16",
                              "status": "FAILED", "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(cells, f, indent=1)
        print(f"wrote {args.out} ({len(cells)} cells, {failures} failures)")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
