"""CLI serving driver: batched or continuous-batching generation on
dense or LC-compressed weights.

    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m \
        --reduced --batch 4 --prompt-len 32 --gen 16 --quantize

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --reduced --engine --form quant4 --slots 4 --requests 12
"""
from __future__ import annotations

import argparse
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import lc_param_paths
from repro.models.transformer import init_params
from repro.runtime.server import (
    Request, Server, ServingEngine, densified_for_serving,
    load_compressed_for_serving, quantize_params_for_serving,
    serving_bits)

FORMS = ("dense", "quant4", "quant8", "lowrank", "sparse")


def compress_for_form(cfg, params, form: str):
    """Bridge the model's 2-D matrices into one serving form via a real
    LC state (direct compression init).

    Returns ``(serving, reference)``: the params in compressed form, and
    the same model with every compressed matrix densified to Δ(Θ), the
    parity reference."""
    from repro.core import AsIs, AsVector, CompressionTask, LCAlgorithm
    from repro.core.schemes import (
        AdaptiveQuantization, ConstraintL0Pruning, LowRank)
    from repro.core.tasks import get_path

    paths = [p for p in lc_param_paths(params)
             if get_path(params, p).ndim == 2]
    assert paths, "no 2-D compressible matrices (use --reduced?)"
    pattern = "|".join(f"^{re.escape(p)}$" for p in paths)
    if form == "quant4":
        task = CompressionTask("q", pattern, AsVector(),
                               AdaptiveQuantization(k=16))
        bits = 4
    elif form == "quant8":
        task = CompressionTask("q", pattern, AsVector(),
                               AdaptiveQuantization(k=64))
        bits = 8
    elif form == "lowrank":
        rank = max(cfg.d_model // 8, 2)
        task = CompressionTask("lr", pattern, AsIs(), LowRank(rank))
        bits = 4
    else:  # sparse
        total = sum(get_path(params, p).size for p in paths)
        task = CompressionTask("pr", pattern, AsVector(),
                               ConstraintL0Pruning(kappa=total // 10))
        bits = 4
    algo = LCAlgorithm([task], [1e-4])
    state = algo.init(params)
    serving, report = load_compressed_for_serving(params, state,
                                                  algo.tasks, bits=bits)
    n = sum(len(f) for f in report.values())
    kinds = sorted({v.split("(")[0] for f in report.values()
                    for v in f.values()})
    print(f"bridged {n} matrices to {form}: forms={kinds}")
    return serving, densified_for_serving(params, state, algo.tasks)


def run_engine(cfg, params, args):
    from repro.runtime import compressed as cforms

    engine = ServingEngine(cfg, params, slots=args.slots,
                           max_len=args.prompt_len + args.gen,
                           prefill_chunk=8)
    rng = np.random.default_rng(0)
    t, reqs = 0.0, []
    for i in range(args.requests):
        t += float(rng.exponential(0.02))
        reqs.append(Request(
            id=i,
            prompt=rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(
                                    4, args.prompt_len + 1)))
            .astype(np.int32),
            max_new=int(rng.integers(2, args.gen + 1)), arrival=t))
    out = engine.run(reqs)
    s = out["stats"]
    print(f"served {s['requests']} requests, {s['tokens']} tokens: "
          f"{s['tokens_per_sec']:.1f} tok/s, "
          f"p50={s['p50_latency_s'] * 1e3:.0f}ms "
          f"p99={s['p99_latency_s'] * 1e3:.0f}ms, "
          f"retraces={ {k: v - 1 for k, v in engine.trace_counts.items()} }")
    print(f"modeled decode HBM/step: "
          f"{cforms.tree_weight_bytes(params)} B")


def run_batch(cfg, params, args, mesh):
    server = Server(cfg, params, mesh=mesh,
                    max_len=args.prompt_len + args.gen)
    prompts = jax.random.randint(
        jax.random.PRNGKey(0), (args.batch, args.prompt_len), 0,
        cfg.vocab_size, jnp.int32)
    t0 = time.perf_counter()
    res = server.generate(prompts, args.gen)
    dt = time.perf_counter() - t0
    print(f"generated {res.tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print("sample:", res.tokens[0][:16])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--quantize", action="store_true",
                    help="legacy: re-k-means quantize then serve the "
                         "dequantized weights")
    ap.add_argument("--form", default="dense", choices=FORMS,
                    help="serve weights in this compressed form "
                         "(bridged from an LC direct-compression state)")
    ap.add_argument("--engine", action="store_true",
                    help="continuous batching over a synthetic Poisson "
                         "trace instead of one equal-length batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine decode slots")
    ap.add_argument("--requests", type=int, default=12,
                    help="engine trace length")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    assert cfg.input_mode == "tokens", "serve CLI expects a token model"
    if args.form != "dense":
        # compressed forms need per-layer (non-stacked) 2-D leaves
        cfg = cfg.with_(pattern_reps=1)

    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.quantize:
        paths = lc_param_paths(params)
        packed, params = quantize_params_for_serving(params, paths)
        comp, dense = serving_bits(packed)
        print(f"quantized {len(paths)} matrices: "
              f"{dense / 8e6:.1f} MB → {comp / 8e6:.1f} MB "
              f"({dense / comp:.1f}× smaller)")
    elif args.form != "dense":
        params, _ = compress_for_form(cfg, params, args.form)

    if args.engine:
        run_engine(cfg, params, args)
    else:
        run_batch(cfg, params, args, make_debug_mesh())


if __name__ == "__main__":
    main()
