import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

# NOTE: no `from __future__ import annotations` here — the XLA_FLAGS export
# above must stay the very first statements, before any jax import.
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=…).lower(**input_specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())   # proves it fits
        print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline

Results (roofline terms, memory, collective schedule) are cached
incrementally to results/dryrun/<cell>.json so the full matrix is
restartable; EXPERIMENTS.md §Dry-run/§Roofline are generated from these.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multi-pod] [--all] [--out results/dryrun]
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.analysis import roofline as rl
from repro.configs import ARCHS, SHAPES, get_config
from repro.distributed.sharding import use_mesh
from repro.launch import inputs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    make_prefill_step, make_serve_step, make_train_step)


def cell_applicable(cfg, shape_cfg) -> tuple[bool, str]:
    if shape_cfg.name == "long_500k" and not cfg.subquadratic:
        return False, ("skip: pure full-attention arch has no sub-quadratic "
                       "mechanism (DESIGN.md §Arch-applicability)")
    return True, ""


def run_cell(arch: str, shape: str, multi_pod: bool,
             extra_cfg: dict | None = None,
             variant: str = "") -> dict:
    """``variant``: ""(paper-faithful baseline) | "fused_attn" |
    "quant_serve" | "fused_attn+quant_serve" — the beyond-paper
    optimizations measured in EXPERIMENTS.md §Perf."""
    cfg = get_config(arch)
    if extra_cfg:
        cfg = cfg.with_(**extra_cfg)
    fused_attn = "fused_attn" in variant
    quant_serve = "quant_serve" in variant
    if fused_attn:
        cfg = cfg.with_(fused_attention=True)
    shape_cfg = SHAPES[shape]
    ok, why = cell_applicable(cfg, shape_cfg)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape, "mesh": mesh_name,
            "variant": variant}
    if not ok:
        return dict(cell, status="skipped", reason=why)

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    skip_scopes = []
    extra_bytes = 0.0
    if fused_attn:
        skip_scopes.append("fused_flash_attention")
        extra_bytes += rl.fused_attention_bytes(cfg, shape_cfg, chips)
    t0 = time.time()
    try:
        with use_mesh(mesh):
            if shape_cfg.kind == "train":
                step = make_train_step(cfg)
                state = specs_mod.train_state_specs(cfg, mesh)
                batch = specs_mod.batch_specs(cfg, shape_cfg, mesh)
                with mesh:
                    # donate the train state: params/opt/LC buffers update
                    # in place (no output copies)
                    lowered = jax.jit(step, donate_argnums=(0,)).lower(
                        state, batch)
                mf = rl.model_flops_train(
                    cfg, shape_cfg.global_batch * shape_cfg.seq_len)
            elif shape_cfg.kind == "decode":
                step = make_serve_step(cfg)
                args = specs_mod.decode_specs(cfg, shape_cfg, mesh,
                                              quantized=quant_serve)
                if quant_serve:
                    skip_scopes.append("fused_quant_matmul")
                    extra_bytes += \
                        specs_mod.quantized_weight_bytes_per_chip(args[0])
                with mesh:
                    # donate the KV cache: in-place ring/linear updates
                    lowered = jax.jit(step, donate_argnums=(1,)).lower(
                        *args)
                mf = rl.model_flops_decode(
                    cfg, shape_cfg.global_batch, shape_cfg.seq_len)
            else:  # prefill
                step = make_prefill_step(cfg)
                args = specs_mod.prefill_specs(cfg, shape_cfg, mesh)
                with mesh:
                    lowered = jax.jit(step).lower(*args)
                mf = rl.model_flops_prefill(
                    cfg, shape_cfg.global_batch * shape_cfg.seq_len)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        print(compiled.memory_analysis())
        ca = compiled.cost_analysis() or {}
        print({k: v for k, v in ca.items()
               if k in ("flops", "bytes accessed")})
        terms = rl.analyze(compiled, arch=arch, shape=shape,
                           mesh_name=mesh_name, chips=chips,
                           model_flops=mf, skip_scopes=tuple(skip_scopes),
                           extra_bytes_per_chip=extra_bytes)
        row = terms.row()
        row.update(status="ok", t_lower_s=t_lower, t_compile_s=t_compile,
                   extra_bytes_per_chip=extra_bytes)
        return dict(cell, **row)
    except Exception as e:  # a failing cell is a bug in our sharding
        return dict(cell, status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-4000:])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run the full arch × shape matrix")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="",
                    help="fused_attn | quant_serve | "
                         "fused_attn+quant_serve")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                suffix = f"__{args.variant}" if args.variant else ""
                path = os.path.join(
                    args.out,
                    f"{arch}__{shape}__{mesh_name}{suffix}.json")
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached] {arch} {shape} {mesh_name}: "
                              f"{prev['status']}")
                        continue
                t0 = time.time()
                res = run_cell(arch, shape, mp, variant=args.variant)
                res["wall_s"] = time.time() - t0
                with open(path, "w") as f:
                    json.dump(res, f, indent=2, default=str)
                status = res["status"]
                extra = ""
                if status == "ok":
                    extra = (f"bottleneck={res['bottleneck']} "
                             f"t=({res['t_compute_s']:.3e},"
                             f"{res['t_memory_s']:.3e},"
                             f"{res['t_collective_s']:.3e})s")
                elif status == "error":
                    extra = res["error"][:200]
                    n_fail += 1
                print(f"[{status}] {arch} {shape} {mesh_name} "
                      f"({res['wall_s']:.0f}s) {extra}", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
