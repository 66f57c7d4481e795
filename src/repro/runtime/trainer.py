"""LCTrainer: the production training loop.

Composes the paper's LC algorithm with the distributed substrate:

    for each LC step k (μ = μ0·aᵏ):
        L step  — ``steps_per_l`` compiled train steps (loss + penalty)
        C step  — jitted sharded projections Θ ← Π(w − λ/μ)
        λ step  — multiplier update
        monitors — L-step loss decrease, C-step distortion decrease (§7)

    throughout: checkpoint every N steps (async), retry transient
    failures, restore-from-checkpoint on hard failure, straggler
    tracking, deterministic seekable data (exact resume).

Host spans and counters (``runtime/spans.py``) time the loop's layers:
each history record carries ``host_ms`` and ``counts`` per name, and a
profile shows the same spans on its host track:

    lc.iteration          one LC iteration, set_mu to the record
      lc.l_step           the L step's steps_per_l optimizer steps
        lc.step           one step's host work: fault check, batch,
                          shard, dispatch (a StepTraceAnnotation)
          lc.step.data    the batch fetch
      lc.drain            until the device has run what the L step queued
      lc.c_step           the C step
    lc.step.starved       counter: steps dispatched after the device had
                          finished the step before

In the overlapped mode (below) ``lc.drain`` is the wait, if any, for the
previous boundary's C step, and ``lc.c_step`` the C step's dispatch. Its
records are emitted later, by ``_apply_pending``, each with the spans of
its own iteration, taken when the iteration's boundary was dispatched.

Two execution modes (``TrainerConfig.overlap``):

* ``"off"`` — the strictly serial loop above: every C step drains the
  accelerator (block_until_ready) before the next L step starts. Simple,
  and the bit-exact reference the overlapped mode is tested against.
* ``"on"`` — the double-buffered pipeline (ROADMAP "Async L/C overlap").
  The C step at an LC boundary depends only on (w, λ, μ), so it is
  dispatched *without blocking* and the next L step begins immediately
  against the previous Δ(Θ)/λ penalty refs; the fresh refs are swapped
  in mid-L-step once the C-step future resolves (or after a fixed
  ``swap_after`` microbatches). The accelerator-idle bubble per μ
  disappears; the cost is a documented stale-refs window — see
  docs/architecture.md ("Async L/C overlap") for the exact semantics
  and the donation rules that make the overlap safe.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, replace
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.algorithm import LCAlgorithm
from repro.core.state import ready_probe
from repro.core.tasks import get_path
from repro.data.pipeline import Prefetcher
from repro.distributed.sharding import resolve_spec, use_mesh
from repro.launch.steps import make_train_step, stable_lc_refs
from repro.optim import AdamW
from repro.runtime.fault_tolerance import (
    FaultInjector, RetryPolicy, StragglerMonitor, is_transient)
from repro.runtime.spans import Spans

log = logging.getLogger("repro.trainer")


@dataclass
class TrainerConfig:
    steps_per_l: int = 20
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    keep_last: int = 3
    lr: float = 3e-4
    clip_norm: float = 1.0
    straggler_factor: float = 3.0
    # paper §7 monitor: the C step must not increase its own objective
    # ‖(w − λ/μ) − Δ(Θ)‖² at fixed (w, λ, μ); violations mean a broken
    # scheme warm start and are logged as errors.
    monitor_distortion: bool = True
    # give up (re-raise) after this many consecutive hard-failure
    # restores with no completed step in between — a deterministic
    # failure would otherwise rewind-and-replay forever.
    max_restores: int = 3
    # async L/C overlap: "off" = serial reference loop (bit-exact with
    # the pre-overlap trainer), "on" = double-buffered pipeline.
    overlap: str = "off"
    # with overlap on: force the ref swap after this many microbatches
    # of the next L step; None = swap as soon as the C-step future
    # resolves (polled non-blockingly between microbatches).
    swap_after: int | None = None
    # kernel dispatch backend for the C step's named scheme solvers
    # ("auto" | "jnp" | "interpret" | "pallas" | "off") — threaded to
    # LCAlgorithm.set_backend when set; None (default) inherits
    # whatever backend the algorithm was constructed with, so an
    # explicit LCAlgorithm(cstep_backend=...) is never clobbered.
    cstep_backend: str | None = None
    # overlap the next L step's first batch construction with the LC
    # boundary dispatch (Prefetcher in data/pipeline.py); the data
    # contract (batch_at pure in step) makes this bit-neutral.
    prefetch_data: bool = True


class LCTrainer:
    def __init__(self, cfg, lc: LCAlgorithm, data, mesh=None,
                 tcfg: TrainerConfig | None = None,
                 optimizer: AdamW | None = None,
                 fault_injector: FaultInjector | None = None,
                 overlap: str | None = None):
        self.cfg = cfg
        self.lc = lc
        self.data = data
        self.mesh = mesh
        if mesh is not None and lc.mesh is None:
            # the trainer owns the mesh: hand it to the algorithm so the
            # grouped C step shards its packed item axes over "data"
            lc.set_mesh(mesh)
        self.tcfg = tcfg or TrainerConfig()
        if overlap is not None:
            self.tcfg = replace(self.tcfg, overlap=overlap)
        if self.tcfg.overlap not in ("off", "on"):
            raise ValueError(
                f"overlap must be 'off' or 'on', got {self.tcfg.overlap!r}")
        if self.tcfg.cstep_backend is not None \
                and self.tcfg.cstep_backend != lc.cstep_backend:
            # an explicit trainer request wins: rebuilds the jitted
            # steps so the solver backend is baked into the C-step HLO
            lc.set_backend(self.tcfg.cstep_backend)
        self._prefetcher = (Prefetcher(data)
                            if self.tcfg.prefetch_data else None)
        self.optimizer = optimizer or AdamW()
        self.retry = RetryPolicy()
        self.straggler = StragglerMonitor(
            factor=self.tcfg.straggler_factor)
        self.faults = fault_injector or FaultInjector()
        self.ckpt = (CheckpointManager(self.tcfg.ckpt_dir,
                                       self.tcfg.keep_last)
                     if self.tcfg.ckpt_dir else None)
        # under a mesh the weights and optimizer state come back
        # replicated, as init_state committed them: left to GSPMD, some
        # optimizer moments return in a layout of their own and the next
        # call compiles the step again. The LC refs pass through as they
        # came.
        rep = None if mesh is None else NamedSharding(mesh, P())
        self._train_step = jax.jit(
            make_train_step(cfg, self.optimizer, lr=self.tcfg.lr,
                            clip_norm=self.tcfg.clip_norm, with_lc=True),
            out_shardings=({"params": rep, "opt": rep, "step": rep,
                            "lc": None}, None))
        self.history: list[dict] = []
        self.spans = Spans()
        # in-flight LC boundary of the overlapped pipeline (None when
        # nothing is in flight / overlap is off)
        self._pending: dict | None = None

    # ------------------------------------------------------------------
    def init_state(self, key):
        from repro.launch.steps import init_train_state
        with use_mesh(self.mesh):
            state = init_train_state(key, self.cfg, self.optimizer,
                                     with_lc=True)
        if self.mesh is not None:
            # commit the state to the mesh, replicated: left uncommitted,
            # the train step's first call compiles a program for an
            # unplaced state that no later call runs
            state = jax.device_put(state, NamedSharding(self.mesh, P()))
        # attach real LC state (Θ, λ) from the algorithm
        lc_state = self.lc.init(state["params"])
        state["lc"] = self._refs_from_lc(state["params"], lc_state)
        self._lc_state = lc_state
        return state

    def _refs_from_lc(self, params, lc_state):
        """Flatten LC (a, λ) into the train-state penalty refs."""
        a, lam = {}, {}
        for t in self.lc.tasks:
            ts = lc_state["tasks"][t.name]
            for p in t.paths:
                a[p] = ts["a"][p]
                lam[p] = ts["lam"][p]
        return {"a": a, "lam": lam, "mu": lc_state["mu"]}

    # ------------------------------------------------------------------
    def _one_step(self, state, step: int):
        with self.spans.span("lc.step", step=step):
            self.faults.maybe_fail(step)
            with self.spans.span("lc.step.data"):
                if self._prefetcher is not None:
                    batch = self._prefetcher.batch_at(step)
                else:
                    batch = self.data.batch_at(step) \
                        if hasattr(self.data, "batch_at") else self.data(step)
            # a poll, not a wait: the device had already finished the
            # step before this one, so it idles until this dispatch
            self.spans.count("lc.step.starved",
                             int(state["step"].is_ready()))
            return self.train_step(state, batch)

    def train_step(self, state, batch):
        """One optimizer step on ``batch``: ``(state, metrics)``. Under a
        mesh it is data-parallel: the batch splits over the mesh's batch
        axes (:meth:`shard_batch`), and the model's activation
        constraints, traced under the mesh, keep it split."""
        with use_mesh(self.mesh):
            return self._train_step(state, self.shard_batch(batch))

    def shard_batch(self, batch):
        """``batch`` split over the mesh's batch axes; as it is without
        a mesh."""
        if self.mesh is None:
            return batch
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(self.mesh, resolve_spec(
                ("batch",) + (None,) * (x.ndim - 1), x.shape, self.mesh))),
            batch)

    def _restore_state(self, state):
        """Hard-failure restore with consistent LC bookkeeping.

        Three things a naive ``ckpt.restore(state)`` leaves wrong, fixed
        here:

        * restored leaves are host numpy — ``jax.device_put`` them back
          onto the shardings of the leaves they replace, so the compiled
          train step keeps its layouts instead of consuming unsharded
          host arrays;
        * the step counter must REWIND to the checkpoint step: the data
          is deterministic and seekable, so training replays from the
          restored weights rather than marching the old counters over
          rewound state;
        * the checkpointed ``state["lc"]`` refs are whatever (μ, λ, Θ)
          was live at save time — re-sync them from the algorithm's
          current LC state at the *current* μ.

        Returns ``(state, next_step)`` where ``next_step`` is the first
        step index to (re)run.
        """
        # elastic-reload path: restore() device_puts every leaf onto the
        # live state's shardings, so no host numpy reaches the train step
        shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, state)
        restored, _ = self.ckpt.restore(state, shardings=shardings)
        # the saved state["step"] is the authoritative resume point (the
        # manifest label is off by one between mid-L-step saves, written
        # after the counter advanced, and final blocking saves)
        next_step = int(np.asarray(restored["step"]))
        refs = self._refs_from_lc(restored["params"], self._lc_state)
        restored["lc"] = dict(refs, mu=state["lc"]["mu"])
        return restored, next_step

    def _l_step(self, state, lc_k: int, global_step: int,
                on_microbatch: Callable | None = None):
        """One full L step = steps_per_l optimizer steps.

        Returns ``(state, last_metrics, next_global_step)``. On a hard
        failure (retries exhausted) the latest checkpoint is restored
        and the step counter rewinds to it (see ``_restore_state``), so
        ``next_global_step`` always equals the step count actually
        reflected in ``state``. ``on_microbatch(state, done) -> state``
        runs after every completed microbatch — the overlapped
        pipeline's swap hook; ``done`` counts microbatches completed in
        this L step.
        """
        metrics = {}
        step = global_step
        end_step = global_step + self.tcfg.steps_per_l
        done = 0
        restores = 0  # consecutive, reset by any completed step
        while step < end_step:
            try:
                state, metrics = self.retry.run(
                    self._one_step, state, step,
                    on_retry=lambda a, e: log.warning(
                        "step %d retry %d: %s", step, a, e))
            except RuntimeError as e:
                if not is_transient(e):
                    raise
                if self.ckpt:
                    # let an in-flight background save commit (and its
                    # errors surface) before deciding whether/where to
                    # restore — latest_step() only sees _COMPLETE dirs
                    self.ckpt.wait()
                if self.ckpt and self.ckpt.latest_step() is not None \
                        and restores < self.tcfg.max_restores:
                    restores += 1
                    log.error("step %d hard failure — restoring (%d/%d)",
                              step, restores, self.tcfg.max_restores)
                    state, step = self._restore_state(state)
                    continue
                raise
            restores = 0
            host_ms = self.spans.last_ms["lc.step"]
            if self.straggler.observe(host_ms):
                log.warning("straggler: step %d took %.1f ms of host time "
                            "(lc.step)", step, host_ms)
            if self.ckpt and step > 0 \
                    and step % self.tcfg.ckpt_every == 0:
                self.ckpt.save(state, step)
            step += 1
            done += 1
            if on_microbatch is not None:
                state = on_microbatch(state, done)
        return state, metrics, step

    # ------------------------------------------------------------------
    def run(self, key, n_lc_steps: int | None = None):
        state = self.init_state(key)
        schedule = self.lc.mu_schedule[:n_lc_steps] \
            if n_lc_steps else self.lc.mu_schedule
        global_step = int(state["step"])

        for g in self.lc.group_summary(state["params"]):
            log.info("c-step group: %s over %s (%d items, tasks=%s, "
                     "spec=%s, padding=%d)",
                     g["scheme"], g["item_shape"], g["items"], g["tasks"],
                     g["spec"], g["padding"])

        if self.tcfg.overlap == "on":
            return self._run_overlapped(state, schedule, global_step)
        return self._run_serial(state, schedule, global_step)

    # ------------------------------------------------------------------
    def _run_serial(self, state, schedule, global_step: int):
        """The reference loop: C step and monitors drain the device at
        every LC boundary. Step-for-step identical to the pre-overlap
        trainer (enforced by tests/test_trainer_overlap.py)."""
        lc_state = self._lc_state
        self.spans.take()  # a record holds its own iteration's spans only
        for k, mu in enumerate(schedule):
            with self.spans.span("lc.iteration"):
                lc_state = self.lc.set_mu(lc_state, mu, k)
                self._lc_state = lc_state
                state["lc"] = self._refs_from_lc(state["params"], lc_state)
                pen0 = float(self.lc.penalty(state["params"], lc_state))

                with self.spans.span("lc.l_step"):
                    state, metrics, global_step = self._l_step(
                        state, k, global_step)

                params = state["params"]
                # drain in-flight L-step work so that lc.c_step times the
                # C step alone, not the async dispatch chain behind it
                with self.spans.span("lc.drain"):
                    if self.tcfg.monitor_distortion:
                        d_pre = self.lc.shifted_distortion(params, lc_state)
                        jax.block_until_ready(d_pre)
                    jax.block_until_ready(params)
                with self.spans.span("lc.c_step"):
                    lc_state = self.lc.c_step(params, lc_state)
                    jax.block_until_ready(lc_state)
                c_violations = []
                if self.tcfg.monitor_distortion:
                    d_post = self.lc.shifted_distortion(params, lc_state)
                    c_violations = self._check_violations(d_pre, d_post)
                lc_state = self.lc.multiplier_step(params, lc_state)
                self._lc_state = lc_state
                state["lc"] = self._refs_from_lc(params, lc_state)

                dist = {n: float(v) for n, v in
                        self.lc.distortion(params, lc_state).items()}
                rec = {
                    "lc_step": k, "mu": float(mu),
                    "loss": float(metrics.get("loss", np.nan)),
                    "ce": float(metrics.get("ce", np.nan)),
                    "penalty_start": pen0,
                    "distortion": dist,
                    "c_step_ms": self.spans.last_ms["lc.c_step"],
                    "c_step_violations": c_violations,
                    "compression_ratio": float(
                        self.lc.compression_ratio(params, lc_state)),
                    "stragglers": self.straggler.stragglers,
                }
            rec.update(self.spans.take())
            self.history.append(rec)
            log.info("LC step %d: %s", k, rec)

        self._lc_state = lc_state
        if self.ckpt:
            self.ckpt.save(state, global_step, blocking=True)
        return state, lc_state

    # ------------------------------------------------------------------
    def _run_overlapped(self, state, schedule, global_step: int):
        """Double-buffered pipeline: dispatch the C step at each LC
        boundary without blocking, run the next L step against the
        previous Δ(Θ)/λ refs, swap the fresh refs in mid-L-step.

        ::

            L step k  ──────────────┤ boundary k ├────────────────────
            C step                  └─ dispatch ──► C(w_k, λ_k, μ_k) ─┐
            L step k+1  [stale refs ....................][fresh refs] │
                                                  swap ◄──────────────┘

        Only the boundary snapshot (w, λ, μ) feeds the C step, so its
        result is independent of the L-step microbatches it overlaps
        with; the first microbatches of L step k+1 simply optimize
        against the previous Δ(Θ)/λ (at the *new* μ — μ is a host
        scalar and advances immediately). Monitors (§7 distortion,
        penalty, compression ratio) are dispatched at the boundary and
        materialized only when the step's record is emitted, so they
        ride the pipeline instead of draining it; ``c_step_ms`` is the
        dispatch→ready wall time of the C+λ chain, measured by polling
        (granularity: one microbatch).
        """
        lc_state = self._lc_state
        self._pending = None  # a prior aborted run must not leak in
        self.spans.take()
        swap_after = self.tcfg.swap_after

        def on_microbatch(st, done):
            if self._pending is None:
                return st
            deadline = swap_after is not None and done >= swap_after
            if deadline or (swap_after is None
                            and self._pending["probe"].is_ready()):
                st = self._apply_pending(st, block=deadline, done=done)
            return st

        for k, mu in enumerate(schedule):
            with self.spans.span("lc.iteration"):
                lc_state = self.lc.set_mu(lc_state, mu, k)
                self._lc_state = lc_state
                if self._pending is None:
                    # cold boundary (first LC step): fresh refs, as serial
                    state["lc"] = self._refs_from_lc(state["params"], lc_state)
                else:
                    # stale-refs window: keep the previous Δ(Θ)/λ in the
                    # penalty while the C step runs; only μ advances now
                    state["lc"] = dict(state["lc"], mu=jnp.float32(mu))
                pen0 = self.lc.penalty(state["params"], lc_state)  # async

                with self.spans.span("lc.l_step"):
                    state, metrics, global_step = self._l_step(
                        state, k, global_step, on_microbatch=on_microbatch)

                # boundary k consumes post-multiplier λ from boundary k-1:
                # if the swap hasn't happened yet (slow C step or large
                # swap_after), force it now
                with self.spans.span("lc.drain"):
                    if self._pending is not None:
                        state = self._apply_pending(
                            state, block=True, done=self.tcfg.steps_per_l)

                # ---- LC boundary k: dispatch everything, block on nothing
                params = state["params"]
                d_pre = (self.lc.shifted_distortion(params, lc_state)
                         if self.tcfg.monitor_distortion else None)
                t_dispatch = time.perf_counter()
                with self.spans.span("lc.c_step"):
                    lc_after_c = self.lc.c_step_async(params, lc_state)
                d_post = (self.lc.shifted_distortion(params, lc_after_c)
                          if self.tcfg.monitor_distortion else None)
                lc_state = self.lc.multiplier_step_async(params, lc_after_c)
                # compression_ratio only reads parameter *shapes* from w —
                # keep shape structs, not the arrays, so the boundary
                # snapshot doesn't pin a second full parameter generation
                # on device for the length of the stale window
                param_shapes = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
                self._pending = {
                    "k": k, "mu": float(mu), "metrics": metrics,
                    "pen0": pen0, "params": param_shapes, "lc_state": lc_state,
                    "d_pre": d_pre, "d_post": d_post,
                    "dist": self.lc.distortion(params, lc_state),
                    "t_dispatch": t_dispatch, "t_ready": None,
                    "probe": ready_probe(lc_state),
                }
                # the C step also overlaps *data loading*: start building
                # the next L step's first microbatch while the boundary
                # chain is in flight (global_step is exactly the step index
                # the next _l_step consumes first). The final boundary has
                # no next L step — don't strand a batch nobody consumes.
                if self._prefetcher is not None and k + 1 < len(schedule):
                    self._prefetcher.prefetch(global_step)
            # the record, emitted later by _apply_pending, holds the spans
            # of this iteration alone
            self._pending["spans"] = self.spans.take()

        # drain the final boundary (no L step left to overlap with);
        # an empty μ schedule never dispatched one
        if self._pending is not None:
            state = self._apply_pending(state, block=True, done=None)
        self._lc_state = lc_state
        if self.ckpt:
            self.ckpt.save(state, global_step, blocking=True)
        return state, lc_state

    def _apply_pending(self, state, block: bool, done: int | None):
        """Swap the in-flight boundary's fresh Δ(Θ)/λ into the penalty
        refs (layout-stable, see ``stable_lc_refs``) and emit the
        finished LC step's record. ``done`` is the microbatch count the
        stale window lasted (None = drained after the final L step)."""
        p = self._pending
        if block:
            jax.block_until_ready(p["probe"])
        if p["t_ready"] is None:
            p["t_ready"] = time.perf_counter()
        refs = self._refs_from_lc(state["params"], p["lc_state"])
        state["lc"] = stable_lc_refs(refs, state["lc"])
        self._pending = None

        c_violations = []
        if p["d_pre"] is not None:
            c_violations = self._check_violations(p["d_pre"], p["d_post"])
        dist = {n: float(v) for n, v in p["dist"].items()}
        rec = {
            "lc_step": p["k"], "mu": p["mu"],
            "loss": float(p["metrics"].get("loss", np.nan)),
            "ce": float(p["metrics"].get("ce", np.nan)),
            "penalty_start": float(p["pen0"]),
            "distortion": dist,
            "c_step_ms": (p["t_ready"] - p["t_dispatch"]) * 1e3,
            "c_step_violations": c_violations,
            "compression_ratio": float(
                self.lc.compression_ratio(p["params"], p["lc_state"])),
            "stragglers": self.straggler.stragglers,
            "swap_after_microbatches": done,
            **p["spans"],
        }
        self.history.append(rec)
        log.info("LC step %d: %s", p["k"], rec)
        return state

    def _check_violations(self, d_pre, d_post) -> list[str]:
        out = []
        for n in d_pre:
            pre, post = float(d_pre[n]), float(d_post[n])
            if post > pre * (1 + 1e-5) + 1e-8:
                out.append(n)
                log.error(
                    "C step increased ‖(w−λ/μ)−Δ(Θ)‖² for task "
                    "%s: %.6g → %.6g (broken warm start?)",
                    n, pre, post)
        return out

    # ------------------------------------------------------------------
    def compressed_params(self, state, lc_state):
        """Final model: w ← Δ(Θ)."""
        from repro.core.tasks import set_path
        params = state["params"]
        for t in self.lc.tasks:
            ts = lc_state["tasks"][t.name]
            for p in t.paths:
                leaf = get_path(params, p)
                params = set_path(params, p,
                                  ts["a"][p].astype(leaf.dtype))
        return params
