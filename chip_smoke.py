#!/usr/bin/env python3
"""Smoke run of the LC compression job and compressed serving on a TPU.

    python chip_smoke.py              # one chip: device, LC job, C-step
                                      # kernels, quant4 serving
    python chip_smoke.py --chips 4    # the mesh path only: the LC job on a
                                      # ("data"=4, "model"=1) mesh vs one chip

Drives xlstm-125m at its published width (d_model 768, 4 heads of 192,
vocab 50304, 12 blocks in a 5:1 mLSTM:sLSTM pattern) through the repo's
own entry points, with random weights and data made from ``--seed``:
``LCTrainer`` over a ``TokenStream`` for the LC job, then
``compress_for_form`` -> ``ServingEngine`` for 4-bit serving. Serving
keeps every width and cuts depth to one period of the pattern (6 blocks),
because the compressed forms need per-layer, unstacked weights.

Every phase asserts its own result and any failure exits non-zero. The
times printed are smoke readings from a single run, not benchmarks. With
no TPU the script exits non-zero before any phase. The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis import cost  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import LCAlgorithm, exponential_mu_schedule  # noqa: E402
from repro.core.schemes.quantize import QuantTheta  # noqa: E402
from repro.core.tasks import get_path  # noqa: E402
from repro.data import TokenStream  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_cstep_mesh, make_debug_mesh  # noqa: E402
from repro.launch.serve import compress_for_form  # noqa: E402
from repro.launch.steps import init_train_state, make_train_step  # noqa: E402
from repro.launch.train import default_tasks  # noqa: E402
from repro.models.transformer import (  # noqa: E402
    decode_step, init_cache, init_params)
from repro.optim import AdamW  # noqa: E402
from repro.runtime import LCTrainer, TrainerConfig  # noqa: E402
from repro.runtime.fault_tolerance import RetryPolicy  # noqa: E402
from repro.runtime.server import (  # noqa: E402
    Request, ServingEngine, engine_programs)

ARCH = "xlstm-125m"
SEQ = 2048
BATCHES = (8, 4, 2, 1)
LC_STEPS = 2
STEPS_PER_L = 3
LR = 1e-3
MU0, MU_A = 9e-5, 1.2                  # launch/train.py defaults
KERNEL_SOLVERS = ("kmeans_lloyd", "topk_mask")
# k-means: the Pallas kernel sums each cluster's moments tile by tile,
# the jnp solver in XLA's reduction order; f32 sums of ~10^6 terms then
# differ in their last bits, which moves a centroid by far less than
# 1e-3 of the codebook's range (the interpret-mode differential bound,
# tests/test_kernel_dispatch.py KMEANS_CB_ATOL at unit-scale weights).
KMEANS_CB_RTOL = 1e-3
# the distortion is stationary in the codebook at a Lloyd fixed point,
# so a centroid shift of 1e-3 moves it by far less than that
KMEANS_DIST_RTOL = 1e-3
# quant4 prefill logits vs the densified Δ(Θ), both in float32 compute,
# the reference at "highest" matmul precision. The packed kernel's
# in-VMEM dot runs at Mosaic's default contract precision: exact f32
# gives ~1e-6, single-pass bf16 operands ~1.6e-2 (both emulated on CPU at
# reduced width, 6 blocks, 64 tokens). A wrong unpack or codebook gives
# an error of order 1.
SERVE_LOGIT_RTOL = 5e-2
# --chips 4: both LC jobs compute in float32, so the mesh job differs
# from the one-device job only in the order of the data-parallel sums:
# at batch 4 on four CPU devices (reduced width) the LC-job losses agree
# to 2.7e-5 and one train step's loss and gradient norm to 5e-7, while a
# planted fault, each shard's batch a copy of the first (the all-reduced
# gradient is one shard's), moves the gradient norm by 1.5. In bfloat16
# the rounding of partial sums grows through Adam's steps (batch 8:
# 6.7e-3 in the losses, 0.14 in the gradient norm), too close to bound.
LOSS_RTOL = 1e-3
STEP_RTOL = 1e-3
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, MAX_NEW = 8, 16, 512, 64
SLOTS, PREFILL_CHUNK = 4, 8


def log(phase: str, msg: str) -> None:
    print(f"[{phase} +{time.perf_counter() - T0:.1f}s] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def tree_bytes(tree) -> int:
    return sum(x.size * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


# ----------------------------------------------------------------------
# 1. device
# ----------------------------------------------------------------------
def device_phase() -> dict:
    devs = jax.devices()
    dev = devs[0]
    log("device", f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        sys.exit(2)
    hw = cost.detect_hardware(devs)
    log("device", f"detect_hardware -> {hw.name}: peak "
        f"{hw.peak_flops:.3g} FLOP/s bf16, HBM {hw.hbm_bw:.3g} B/s, "
        f"{gib(hw.hbm_bytes)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ----------------------------------------------------------------------
# 2. LC job
# ----------------------------------------------------------------------
def make_lc(cfg) -> LCAlgorithm:
    return LCAlgorithm(default_tasks(cfg, "prune"),
                       exponential_mu_schedule(MU0, MU_A, LC_STEPS))


def train_state_shapes(cfg, lc: LCAlgorithm, optimizer):
    """Abstract train state as ``LCTrainer.init_state`` lays it out: the
    penalty refs cover the task paths only."""
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda k: init_train_state(k, cfg, optimizer, with_lc=True), key)
    lc.resolve(shapes["params"])
    f32 = {p: jax.ShapeDtypeStruct(get_path(shapes["params"], p).shape,
                                   jnp.float32)
           for t in lc.tasks for p in t.paths}
    shapes["lc"] = {"a": f32, "lam": dict(f32),
                    "mu": jax.ShapeDtypeStruct((), jnp.float32)}
    return shapes


def fit_batch(cfg, lc: LCAlgorithm, optimizer, dev) -> tuple[int, float]:
    """Largest batch in BATCHES whose AOT-compiled train step, next to
    the LC state's Θ, fits the device; returns (batch, compile seconds)."""
    shapes = train_state_shapes(cfg, lc, optimizer)
    lc_shapes = jax.eval_shape(lc.init, shapes["params"])
    theta_bytes = tree_bytes({n: ts["theta"]
                              for n, ts in lc_shapes["tasks"].items()})
    limit = dev.memory_stats()["bytes_limit"]
    step = jax.jit(make_train_step(cfg, optimizer, lr=LR, with_lc=True))
    for b in BATCHES:
        tok = jax.ShapeDtypeStruct((b, SEQ), jnp.int32)
        t0 = time.perf_counter()
        try:
            compiled = step.lower(shapes, {"inputs": tok,
                                           "labels": tok}).compile()
        except Exception as e:  # noqa: BLE001 - the compiler's refusal
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log("lc", f"batch {b}: does not fit (compiler: "
                f"{str(e).splitlines()[0][:160]})")
            continue
        secs = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes
                + theta_bytes)
        fits = need <= limit
        log("lc", f"batch {b}: train step + Θ need {gib(need)} of "
            f"{gib(limit)} -> {'fits' if fits else 'does not fit'} "
            f"(compile {secs:.1f} s)")
        if fits:
            return b, secs
    raise AssertionError("no batch size fits the device")


def run_lc_job(cfg, batch: int, mesh, tag: str,
               lc: LCAlgorithm | None = None):
    """One LC job through LCTrainer; asserts the job's own health checks.
    Returns (trainer, state, lc_state, wall seconds)."""
    data = TokenStream(cfg.vocab_size, batch, SEQ, seed=ARGS.seed)
    trainer = LCTrainer(cfg, lc or make_lc(cfg), data, mesh=mesh,
                        tcfg=TrainerConfig(steps_per_l=STEPS_PER_L,
                                           lr=LR))
    # no retry and no checkpoint: any step failure surfaces at once
    trainer.retry = RetryPolicy(max_retries=0)
    t0 = time.perf_counter()
    state, lc_state = trainer.run(jax.random.PRNGKey(ARGS.seed))
    jax.block_until_ready((state, lc_state))
    wall = time.perf_counter() - t0
    check(len(trainer.history) == LC_STEPS, "LC steps missing")
    for rec in trainer.history:
        log(tag, f"LC step {rec['lc_step']}: mu={rec['mu']:.4g} "
            f"loss={rec['loss']:.6f} c_step_ms={rec['c_step_ms']:.1f} "
            f"ratio={rec['compression_ratio']:.3f} "
            f"violations={rec['c_step_violations']}")
        check(math.isfinite(rec["loss"]), f"loss not finite: {rec}")
        check(not rec["c_step_violations"],
              f"C-step violations: {rec['c_step_violations']}")
        check(rec["compression_ratio"] > 1, f"ratio <= 1: {rec}")
    check(trainer.faults.injected == 0, "a fault was injected")
    log(tag, "retries: 0 (RetryPolicy(max_retries=0)); restores: 0 "
        "(no checkpoint directory)")
    return trainer, state, lc_state, wall


def lc_phase(cfg, dev):
    n = sum(x.size for x in jax.tree_util.tree_leaves(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0))))
    log("lc", f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads x "
        f"{cfg.head_dim}, vocab {cfg.vocab_size}, {cfg.n_layers} blocks "
        f"(pattern {len(cfg.pattern)} x {cfg.pattern_reps}), {n} f32 "
        f"params; not cut; seq {SEQ}, remat={cfg.remat}")
    batch, compile_s = fit_batch(cfg, make_lc(cfg), AdamW(), dev)
    log("lc", f"batch {batch} (largest power of two <= 8 that fits)")
    trainer, state, lc_state, wall = run_lc_job(cfg, batch,
                                                make_debug_mesh(), "lc")
    # steady step time: three more L steps on the trained state (the
    # first compiles), each drained with block_until_ready
    step = jax.jit(make_train_step(cfg, trainer.optimizer, lr=LR,
                                   with_lc=True))
    times = []
    for i in range(3):
        b = trainer.data.batch_at(10_000 + i)
        t0 = time.perf_counter()
        state, _ = step(state, b)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
    stats = dev.memory_stats()
    log("lc", "smoke reading, one run, not a benchmark: "
        f"train-step compile {compile_s:.1f} s; steady step "
        f"{min(times[1:]):.4f} s (batch {batch} x {SEQ} tokens); "
        f"c_step_ms {[round(r['c_step_ms'], 1) for r in trainer.history]}; "
        f"job wall {wall:.1f} s; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return trainer, state, lc_state


# ----------------------------------------------------------------------
# 3. C-step kernels
# ----------------------------------------------------------------------
def kernel_phase(trainer, state, lc_state):
    lc = trainer.lc
    params = state["params"]
    summary = lc.group_summary(params)
    on_pallas = []
    for g in summary:
        plan = g["plan"] or {}
        log("kernels", f"group {g['tasks'][0]}..({len(g['tasks'])} tasks, "
            f"{g['items']} items of {g['item_shape']}): "
            f"solver={g['solver']} backend={g['backend']} "
            f"block_rows={plan.get('block_rows')} "
            f"chunks={plan.get('n_chunks')} "
            f"fallbacks={list(plan.get('fallbacks', ()))}")
        if g["solver"] in KERNEL_SOLVERS and g["backend"] == "pallas":
            on_pallas.append(g["solver"])
    if on_pallas:
        text = lc._c_step.lower(params, lc_state).compile().as_text()
        n_calls = text.count("tpu_custom_call")
        log("kernels", f"compiled C step: {n_calls} tpu_custom_call "
            f"sites for {sorted(set(on_pallas))}")
        check(n_calls > 0, "C step routed to pallas but its compiled "
              "text has no tpu_custom_call")
    else:
        log("kernels", "the planner routed no group to pallas; the "
            "comparison below forces cstep_backend='pallas'")

    outs = {}
    for backend in ("pallas", "jnp"):
        lc.set_backend(backend)
        out = lc.c_step_async(params, lc_state)
        outs[backend] = (out, lc.distortion(params, out))
        jax.block_until_ready(outs[backend])
    lc.set_backend("auto")
    (out_p, dist_p), (out_j, dist_j) = outs["pallas"], outs["jnp"]
    solvers = set()
    worst_cb = worst_dist = 0.0
    for t in lc.tasks:
        tp, tj = out_p["tasks"][t.name]["theta"], \
            out_j["tasks"][t.name]["theta"]
        if isinstance(tj, QuantTheta):
            solvers.add("kmeans_lloyd")
            cj = np.asarray(tj.codebook)
            cb_err = float(np.max(np.abs(np.asarray(tp.codebook) - cj))
                           / np.max(np.abs(cj)))
            dj = float(dist_j[t.name])
            d_err = abs(float(dist_p[t.name]) - dj) / dj
            worst_cb, worst_dist = max(worst_cb, cb_err), \
                max(worst_dist, d_err)
        else:
            solvers.add("topk_mask")
            mp = np.asarray(tp["theta"]) != 0
            mj = np.asarray(tj["theta"]) != 0
            check(np.array_equal(mp, mj),
                  f"{t.name}: top-κ masks differ in "
                  f"{int(np.sum(mp != mj))} places")
    log("kernels", f"pallas vs jnp C step: k-means codebooks max rel "
        f"{worst_cb:.3g} (bound {KMEANS_CB_RTOL}), distortion max rel "
        f"{worst_dist:.3g} (bound {KMEANS_DIST_RTOL}); top-κ masks equal")
    check(solvers == set(KERNEL_SOLVERS), f"solvers covered: {solvers}")
    check(worst_cb <= KMEANS_CB_RTOL, "k-means codebooks differ")
    check(worst_dist <= KMEANS_DIST_RTOL, "k-means distortion differs")


# ----------------------------------------------------------------------
# 4. compressed serving
# ----------------------------------------------------------------------
def serve_phase(cfg_full):
    cfg = cfg_full.with_(pattern_reps=1)
    log("serve", f"{cfg.name} quant4: every width kept, depth cut to "
        f"pattern_reps=1 ({cfg.n_layers} blocks), because the compressed "
        f"forms need unstacked per-layer weights")
    params = init_params(jax.random.PRNGKey(ARGS.seed), cfg)
    serving, reference = compress_for_form(cfg, params, "quant4")
    del params
    rng = np.random.default_rng(ARGS.seed)
    reqs = [Request(id=i,
                    prompt=rng.integers(1, cfg.vocab_size, size=int(
                        rng.integers(PROMPT_MIN, PROMPT_MAX + 1)))
                    .astype(np.int32),
                    max_new=int(rng.integers(1, MAX_NEW + 1)),
                    arrival=0.0)
            for i in range(N_REQUESTS)]
    max_len = PROMPT_MAX + MAX_NEW
    engine = ServingEngine(cfg, serving, slots=SLOTS, max_len=max_len,
                           prefill_chunk=PREFILL_CHUNK, seed=ARGS.seed)
    t0 = time.perf_counter()
    out = engine.run(reqs)
    wall = time.perf_counter() - t0
    done = {f.id: f for f in out["finished"]}
    check(not out["rejected"], f"rejected: {[r.id for r in out['rejected']]}")
    check(sorted(done) == list(range(N_REQUESTS)), "requests unfinished")
    for r in reqs:
        check(len(done[r.id].tokens) == r.max_new, f"request {r.id} short")
    retraces = {k: v - 1 for k, v in engine.trace_counts.items()}
    log("serve", f"{N_REQUESTS} requests (prompts "
        f"{sorted(len(r.prompt) for r in reqs)}, max_new "
        f"{[r.max_new for r in reqs]}) all finished; recompiles after "
        f"warm-up {retraces}")
    check(all(v == 0 for v in retraces.values()), "engine recompiled")
    log("serve", f"smoke reading, one run, not a benchmark: wall "
        f"{wall:.1f} s incl. compile, {out['stats']['tokens']} tokens")

    counts = {"decode": 0, "prefill": 0, "reset": 0}
    decode_impl, _, _ = engine_programs(cfg, SLOTS, max_len, 0.0, counts)
    vec = jnp.zeros((SLOTS,), jnp.int32)
    text = jax.jit(decode_impl, donate_argnums=(1,)).lower(
        serving, init_cache(cfg, SLOTS, max_len), vec, vec,
        jnp.ones((SLOTS,), bool), jax.random.PRNGKey(0)).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    log("serve", f"compiled decode program: {n_calls} tpu_custom_call "
        f"sites (packed 4-bit GEMM)")
    check(n_calls > 0, "decode runs no Pallas kernel")

    cfg32 = cfg.with_(dtype="float32")

    @jax.jit
    def prefill_logits(p, prompt):
        # the engine's prefill: one decode step per prompt token
        def body(cache, xs):
            tok, pos = xs
            logits, cache = decode_step(p, cache, tok[None, None], pos,
                                        cfg32)
            return cache, logits[0, 0]

        n = prompt.shape[0]
        return jax.lax.scan(body, init_cache(cfg32, 1, n),
                            (prompt, jnp.arange(n, dtype=jnp.int32)))[1]

    prompt = jnp.asarray(reqs[0].prompt)
    lq = np.asarray(prefill_logits(serving, prompt))
    with jax.default_matmul_precision("highest"):
        ld = np.asarray(prefill_logits(reference, prompt))
    check(np.all(np.isfinite(lq)), "quant4 logits not finite")
    rel = float(np.linalg.norm(lq - ld) / np.linalg.norm(ld))
    agree = float(np.mean(lq.argmax(-1) == ld.argmax(-1)))
    log("serve", f"first request's prefill ({prompt.shape[0]} tokens), "
        f"float32 compute: logits quant4 vs densified Δ(Θ) relative norm "
        f"error {rel:.3g} (bound {SERVE_LOGIT_RTOL}); argmax agrees at "
        f"{agree:.3f} of positions")
    check(rel <= SERVE_LOGIT_RTOL, "quant4 logits off the reference")


# ----------------------------------------------------------------------
# --chips 4: the mesh path
# ----------------------------------------------------------------------
def compare_csteps(lc4, lc1, params, lc_state, one_mesh):
    """One C step on the default "auto" route from the same state on the
    mesh and on one device; returns the Θ and Δ(Θ) leaves that are not bit-identical."""
    # the C step donates its LC state, and moving a replicated array to
    # device 0 hands over the buffer of its shard there: pass copies
    to1 = jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.copy(x), NamedSharding(one_mesh, P())),
        (params, lc_state))
    out1 = lc1.resolve(to1[0]).c_step(*to1)
    out4 = lc4.c_step(params, jax.tree_util.tree_map(jnp.copy, lc_state))
    n_leaves, differ = 0, []
    for t in lc4.tasks:
        for part in ("theta", "a"):
            for a4, a1 in zip(
                    jax.tree_util.tree_leaves(out4["tasks"][t.name][part]),
                    jax.tree_util.tree_leaves(out1["tasks"][t.name][part])):
                n_leaves += 1
                if not np.array_equal(np.asarray(a4), np.asarray(a1)):
                    differ.append(f"{t.name}/{part}")
    log("mesh", f"C step (auto) on 4 chips vs 1: {n_leaves} Θ and Δ(Θ) "
        f"leaves, {len(differ)} differ" + (f": {differ}" if differ else ""))
    return differ


def step_reading(trainer, state, batch) -> dict:
    """Loss and pre-clip gradient norm of one train step."""
    _, m = trainer.train_step(state, batch)
    return {k: float(m[k]) for k in ("loss", "grad_norm")}


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


def mesh_phase(cfg):
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = cfg.with_(dtype="float32")
    # float32 doubles the activations: the one-device job at batch 8 ran
    # out of memory on a v5e, though its AOT compile asks 12.0 GiB of
    # 15.75; at batch 4 it asks 8.4
    batch = 4
    log("mesh", f"global batch {batch} x {SEQ} on both jobs, float32 "
        "compute (see LOSS_RTOL)")
    mesh, one_mesh = make_cstep_mesh(), make_debug_mesh()
    # the one-device job first, while device 0 holds nothing else: it
    # needs most of the chip, the mesh job a quarter of the batch
    one = make_lc(cfg).set_mesh(one_mesh)
    tr1, st1, _, wall1 = run_lc_job(cfg, batch, one_mesh, "one", one)
    losses1 = [r["loss"] for r in tr1.history]
    c_ms1 = [round(r["c_step_ms"], 1) for r in tr1.history]
    # one train step from the job's final state on the next batch; the
    # mesh takes the same step from the same state below
    b = tr1.data.batch_at(LC_STEPS * STEPS_PER_L)
    step1 = step_reading(tr1, st1, b)
    host = jax.device_get(st1)
    del tr1, st1
    gc.collect()
    in_use = (devs[0].memory_stats() or {}).get("bytes_in_use")
    log("mesh", f"device 0 bytes in use after the one-device job: {in_use}")

    log("mesh", f"LC job on mesh {dict(mesh.shape)}, C steps on 'auto'")
    tr4, st4, lcs4, wall4 = run_lc_job(cfg, batch, mesh, "mesh")
    losses4 = [r["loss"] for r in tr4.history]
    c_ms4 = [round(r["c_step_ms"], 1) for r in tr4.history]
    params = st4["params"]
    for g in tr4.lc.group_summary(params):
        log("mesh", f"group {g['tasks'][0]}..: items={g['items']} "
            f"spec={g['spec']} shard_mode={g['shard_mode']} "
            f"backend={g['backend']}")
    # every check below runs after every reading is printed
    differ = compare_csteps(tr4.lc, one, params, lcs4, one_mesh)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, st4)
    del st4, lcs4, params
    gc.collect()
    # the data-parallel step, and the same step with a planted fault:
    # every shard of the batch a copy of the first, so the all-reduced
    # gradient is the first shard's alone
    state = jax.device_put(host, shardings)
    n = mesh.shape["data"]
    step4 = step_reading(tr4, state, b)
    fault = step_reading(tr4, state, {
        k: jnp.tile(v[:v.shape[0] // n], (n, 1)) for k, v in b.items()})
    del tr4, state, host

    sound = max(rel_err(x, y) for x, y in zip(losses4, losses1))
    log("mesh", f"LC-job losses 4 chips {losses4} vs 1 chip {losses1}: "
        f"max rel {sound:.3g} (bound {LOSS_RTOL})")
    step_rel = {k: rel_err(step4[k], step1[k]) for k in step1}
    fault_rel = {k: rel_err(fault[k], step1[k]) for k in step1}
    log("mesh", f"one train step from the same state: 4 chips {step4}, "
        f"1 chip {step1}, rel {step_rel} (bound {STEP_RTOL}); planted "
        f"one-shard gradient {fault}, rel {fault_rel}")
    log("mesh", "smoke reading, one run, not a benchmark: job wall 4 chips "
        f"{wall4:.1f} s, 1 chip {wall1:.1f} s; c_step_ms 4 chips {c_ms4}, "
        f"1 chip {c_ms1}")
    check(not differ, f"mesh C step differs from one device: {differ}")
    check(sound <= LOSS_RTOL, "mesh LC-job losses off the one-chip job")
    check(max(step_rel.values()) <= STEP_RTOL,
          "mesh train step off the one-chip step")
    check(fault_rel["grad_norm"] > STEP_RTOL,
          "the step bound does not catch a gradient from one shard only")


def main() -> None:
    global ARGS
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ARGS = ap.parse_args()
    enable_compile_cache()
    device = device_phase()
    dev = jax.devices()[0]
    cfg = get_config(ARCH)
    if ARGS.chips == 4:
        mesh_phase(cfg)
    else:
        trainer, state, lc_state = lc_phase(cfg, dev)
        kernel_phase(trainer, state, lc_state)
        del trainer, state, lc_state
        serve_phase(cfg)
    print(json.dumps({"ok": True, "device": device}), flush=True)


ARGS = None
T0 = time.perf_counter()

if __name__ == "__main__":
    main()
