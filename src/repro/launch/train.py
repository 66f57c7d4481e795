"""CLI training driver.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --reduced --lc-steps 4 --steps-per-l 10 --batch 4 --seq 128

Runs LC-compressed training end-to-end: data stream → L steps (compiled
train step with the LC penalty) → C steps → multipliers, with
checkpointing and fault tolerance. ``--reduced`` uses the smoke config
(CPU-sized); full configs expect a real TPU mesh.
"""
from __future__ import annotations

import argparse
import logging
import re

import jax

from repro.configs import ARCHS, get_config, reduced_config
from repro.core import (
    AsStacked, AsVector, CompressionTask, LCAlgorithm,
    exponential_mu_schedule)
from repro.core.schemes import AdaptiveQuantization, ConstraintL0Pruning
from repro.core.tasks import flatten_params
from repro.data import TokenStream, embedding_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.transformer import init_params
from repro.runtime import FaultInjector, LCTrainer, TrainerConfig

#: stacked attention and gated-FFN projections, and the other stacked
#: projections (mixer in/out and xLSTM)
_ATTN_FFN = "w_gate|w_up|w_down|wq|wk|wv|wo"
_OTHER = "in_proj|out_proj|up_proj|down_proj|w"


def default_tasks(cfg, compression: str = "quantize"):
    """The flagship per-arch compression tasks.

    * ``"quantize"``: per-layer adaptive 4-bit codebooks on every
      stacked projection (AsStacked ⇒ one item per layer).
    * ``"prune"``: one ℓ0 budget keeping the largest tenth of the
      attention and gated-FFN stacks' weights, plus 4-bit codebooks on
      the other stacked projections (tasks must not share parameters).
    """
    def quantize(names):
        return CompressionTask(
            "quantize-stacks", rf"stages/.*/({names})$",
            AsStacked("vector"), AdaptiveQuantization(k=16, iters=10))

    if compression == "quantize":
        return [quantize(f"{_ATTN_FFN}|{_OTHER}")]
    if compression == "prune":
        pattern = rf"stages/.*/({_ATTN_FFN})$"
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        total = sum(leaf.size for p, leaf in flatten_params(shapes).items()
                    if re.search(pattern, p))
        return [CompressionTask("prune-stacks", pattern, AsVector(),
                                ConstraintL0Pruning(kappa=max(1, total // 10))),
                quantize(_OTHER)]
    raise ValueError(compression)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lc-steps", type=int, default=3)
    ap.add_argument("--steps-per-l", type=int, default=5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mu0", type=float, default=9e-5)
    ap.add_argument("--mu-a", type=float, default=1.2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)

    if cfg.input_mode == "tokens":
        data = TokenStream(cfg.vocab_size, args.batch, args.seq)
    else:
        fn = embedding_stream(args.batch, args.seq, cfg.d_input,
                              cfg.vocab_size)
        class _D:  # noqa: N801
            batch_at = staticmethod(fn)
        data = _D()

    lc = LCAlgorithm(
        default_tasks(cfg),
        exponential_mu_schedule(args.mu0, args.mu_a, args.lc_steps))
    mesh = make_debug_mesh()
    trainer = LCTrainer(
        cfg, lc, data, mesh=mesh,
        tcfg=TrainerConfig(steps_per_l=args.steps_per_l, lr=args.lr,
                           ckpt_dir=args.ckpt_dir),
        fault_injector=FaultInjector())
    state, lc_state = trainer.run(jax.random.PRNGKey(0))
    for rec in trainer.history:
        print(rec)
    print("final compression ratio:",
          trainer.history[-1]["compression_ratio"])


if __name__ == "__main__":
    main()
