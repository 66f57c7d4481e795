"""The LC algorithm driver (paper Fig. 2).

    w ← argmin_w L(w)                                  (pretrained model)
    Θ ← Π(w̄)                                           (direct compression)
    λ ← 0
    for μ = μ0 < μ1 < … :
        w ← argmin_w L(w) + μ/2‖w − Δ(Θ) − λ/μ‖²       (L step — user fn)
        Θ ← argmin_Θ ‖w − λ/μ − Δ(Θ)‖²                 (C step — schemes)
        λ ← λ − μ(w − Δ(Θ))                            (multipliers)
        stop when ‖w − Δ(Θ)‖ small

The L step is handed to the user as a *compiled step function + step
count* (not an opaque Python loop) so the trainer can pjit it, checkpoint
mid-L-step, and apply fault-tolerance policies.

The C step is ONE jitted call. With ``group_tasks=True`` (default) the
independent per-task projections are not merely traced side by side: tasks
with equal ``scheme.group_key()`` and item shape are stacked along a
leading axis and solved by a single vmapped scheme program per group
(``core.grouping``) — the paper's "C steps can be run in parallel" note,
realized as batched compute instead of N copies of the same HLO. The LC
state buffers are donated to the C/multiplier steps on accelerators, so
Θ/λ/a update in place. ``group_tasks=False`` keeps the legacy per-task
trace for schemes that cannot be vmapped.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import state as lcstate
from repro.core.grouping import (
    _constrain_replicated, describe_groups, grouped_compress, grouped_init,
    solve_task)
from repro.core.penalty import lc_penalty
from repro.core.tasks import CompressionTask, check_disjoint, get_path
from repro.core.views import AsVector


def exponential_mu_schedule(mu0: float, a: float, n_steps: int):
    """μ_k = μ0·a^k (paper §7: a ∈ [1.1, 1.4])."""
    return [mu0 * a**k for k in range(n_steps)]


@dataclass
class LCMetrics:
    step: int
    mu: float
    distortion: dict[str, float]      # per task: ‖w − Δ(Θ)‖²
    penalty: float
    compression_ratio: float


class LCAlgorithm:
    """Orchestrates L/C/multiplier steps over a params pytree."""

    def __init__(self, tasks: Sequence[CompressionTask],
                 mu_schedule: Sequence[float],
                 l_step: Callable | None = None,
                 eval_fn: Callable | None = None,
                 jit_c_step: bool = True,
                 group_tasks: bool = True,
                 donate: bool | str = "auto",
                 mesh=None,
                 sharding_rules: dict | None = None,
                 cstep_backend: str = "auto",
                 planner: str | None = "on"):
        self.tasks = list(tasks)
        self.mu_schedule = list(mu_schedule)
        self.l_step = l_step
        self.eval_fn = eval_fn
        self.group_tasks = bool(group_tasks)
        self.mesh = mesh
        self.sharding_rules = sharding_rules
        # kernel dispatch backend for opted-in scheme solvers
        # ("auto" | "jnp" | "interpret" | "pallas" | "off"); resolved
        # per group by repro.kernels.dispatch — see docs/architecture.md
        self.cstep_backend = self._check_backend(cstep_backend)
        # roofline-guided group planner ("on" | "off" | None≡"off"):
        # picks backend/tile/chunking per group at trace time and
        # memoizes the decision — see repro.analysis.cost
        self.planner = self._check_planner(planner)
        if donate == "auto":
            # donation is a no-op (with a warning) on CPU; only ask for
            # in-place Θ/λ/a updates where XLA implements aliasing.
            donate = jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")
        self._jit_c_step = bool(jit_c_step)
        self._donate = bool(donate)
        self._build_steps()
        self._resolved = False

    def _build_steps(self):
        """(Re)wrap the step impls in jit; called again by set_mesh so a
        late-bound mesh invalidates any already-compiled executables."""
        dargs = (1,) if self._donate else ()
        if self._jit_c_step:
            self._c_step = jax.jit(self._c_step_impl, donate_argnums=dargs)
            self._mult_step = jax.jit(self._multiplier_step_impl,
                                      donate_argnums=dargs)
            self._distortion = jax.jit(self._distortion_impl)
            self._shifted_distortion = jax.jit(
                self._shifted_distortion_impl)
        else:
            self._c_step = self._c_step_impl
            self._mult_step = self._multiplier_step_impl
            self._distortion = self._distortion_impl
            self._shifted_distortion = self._shifted_distortion_impl
        # Async (overlap-safe) variants: NEVER donate. While an
        # overlapped L step is in flight it still reads the previous
        # Θ/λ/a buffers through its penalty refs, so donating them to
        # the concurrent C/multiplier step would let XLA overwrite
        # memory another executable is reading. When donation is off
        # anyway, the sync and async entry points share one executable.
        if self._donate and self._jit_c_step:
            self._c_step_async = jax.jit(self._c_step_impl)
            self._mult_step_async = jax.jit(self._multiplier_step_impl)
        else:
            self._c_step_async = self._c_step
            self._mult_step_async = self._mult_step
        # grouped Θ^DC cold start: one jitted program, one scheme trace
        # per group (never donates — params are the caller's)
        self._init_grouped = (jax.jit(self._init_grouped_impl)
                              if self._jit_c_step
                              else self._init_grouped_impl)

    def set_mesh(self, mesh, rules: dict | None = None) -> "LCAlgorithm":
        """Bind the device mesh the grouped C step shards over.

        The mesh is static trace-time state (it picks the sharding
        constraints baked into the C-step HLO), so the jitted steps are
        rebuilt — safe to call any time, typically right after
        construction by the trainer that owns the mesh.
        """
        self.mesh = mesh
        if rules is not None:
            self.sharding_rules = rules
        self._build_steps()
        return self

    @staticmethod
    def _check_backend(backend):
        """Fail fast on a typo'd backend: the first consumer would
        otherwise be dispatch.resolve_backend inside the first C-step
        trace, minutes into a run and wrapped in a jit traceback."""
        valid = (None, "auto", "jnp", "interpret", "pallas", "off")
        if backend not in valid:
            raise ValueError(
                f"cstep_backend must be one of {valid[1:]}, "
                f"got {backend!r}")
        return backend

    @staticmethod
    def _check_planner(planner):
        valid = (None, "on", "off")
        if planner not in valid:
            raise ValueError(
                f"planner must be one of {valid}, got {planner!r}")
        return planner

    def set_planner(self, planner: str | None) -> "LCAlgorithm":
        """Toggle the roofline group planner. Trace-time state like
        :meth:`set_backend` (it decides which solver impl / tiling /
        chunking the C-step HLO bakes in), so the steps are rebuilt."""
        self.planner = self._check_planner(planner)
        self._build_steps()
        return self

    def set_backend(self, backend: str) -> "LCAlgorithm":
        """Select the kernel dispatch backend for the C step.

        Like :meth:`set_mesh` this is trace-time state (it decides
        which solver implementations the C-step HLO bakes in), so the
        jitted steps are rebuilt.
        """
        self.cstep_backend = self._check_backend(backend)
        self._build_steps()
        return self

    # ------------------------------------------------------------------
    def resolve(self, params):
        if not self._resolved:
            resolved = []
            for t in self.tasks:
                t = t.resolve(params)
                if len(t.paths) > 1 and not isinstance(t.view, AsVector):
                    # single-array views (AsIs/AsMatrix/AsStacked) over a
                    # multi-leaf selector = one independent task per leaf
                    # (paper semantics: per-layer compression)
                    for i, p in enumerate(t.paths):
                        resolved.append(CompressionTask(
                            f"{t.name}[{i}]", t.pattern, t.view,
                            t.scheme, [p]))
                else:
                    resolved.append(t)
            self.tasks = resolved
            check_disjoint(self.tasks)
            self._resolved = True
        return self

    def init(self, params) -> dict:
        """Θ ← Π(w̄), λ ← 0 (direct compression).

        With ``group_tasks=True`` (default) the Θ^DC solves run through
        :func:`grouped_init` inside one jitted program — one scheme
        trace per (scheme, item shape) group, so cold-start compile
        cost is O(groups) like the C step's (and the packed item axes
        shard over a bound mesh). ``group_tasks=False`` keeps the
        legacy eager per-task loop; both produce identical state.
        """
        self.resolve(params)
        if self.group_tasks:
            return self._init_grouped(params)
        tasks_state = {}
        for t in self.tasks:
            theta = t.scheme_init(t.compressible(params))
            a = t.scatter_decompressed(t.scheme_decompress(theta), params)
            lam = lcstate.zeros_like_leaves(t.paths, t.leaves(params))
            tasks_state[t.name] = lcstate.task_state(theta, lam, a)
        return lcstate.lc_state(tasks_state, self.mu_schedule[0], k=0)

    def _init_grouped_impl(self, params):
        xs = {t.name: t.compressible(params) for t in self.tasks}
        results = grouped_init(self.tasks, xs, mesh=self.mesh,
                               rules=self.sharding_rules)
        tasks_state = {}
        for t in self.tasks:
            theta, a_arr = results[t.name]
            a = t.scatter_decompressed(a_arr, params)
            lam = lcstate.zeros_like_leaves(t.paths, t.leaves(params))
            tasks_state[t.name] = lcstate.task_state(theta, lam, a)
        return lcstate.lc_state(tasks_state, self.mu_schedule[0], k=0)

    # ------------------------------------------------------------------
    def _c_step_impl(self, params, lc):
        if self.group_tasks:
            return self._c_step_grouped(params, lc)
        return self._c_step_pertask(params, lc)

    def _c_step_pertask(self, params, lc):
        """Per-task path: one scheme trace per task (`group_tasks=False`).

        Kernel dispatch still applies — each opted-in task's solve runs
        through its named batched solver on a 1-task item stack — so
        the kernel path is exercised on both dispatch modes."""
        mu = lc["mu"]
        new_tasks = {}
        for t in self.tasks:
            ts = lc["tasks"][t.name]
            x = t.shifted_compressible(params, ts, mu)
            theta = solve_task(t, x, ts["theta"], mu,
                               backend=self.cstep_backend)
            a = t.scatter_decompressed(t.scheme_decompress(theta), params)
            new_tasks[t.name] = lcstate.task_state(theta, ts["lam"], a)
        return lcstate.with_tasks(lc, new_tasks)

    def _c_step_grouped(self, params, lc):
        """Grouped path: one vmapped scheme trace per (scheme, shape)
        group — see ``core.grouping``. With ``self.mesh`` set, each
        group's packed item axis is sharded over the mesh's data axis.
        Bitwise-equivalent to the per-task path and to ``mesh=None``
        (enforced by tests/test_grouped_cstep.py and
        tests/test_sharded_cstep.py)."""
        mu = lc["mu"]
        xs = {t.name: t.shifted_compressible(params, lc["tasks"][t.name],
                                             mu)
              for t in self.tasks}
        thetas = {t.name: lc["tasks"][t.name]["theta"]
                  for t in self.tasks}
        results = grouped_compress(self.tasks, xs, thetas, mu,
                                   mesh=self.mesh,
                                   rules=self.sharding_rules,
                                   backend=self.cstep_backend,
                                   planner=self.planner)
        new_tasks = {}
        for t in self.tasks:
            theta, a_arr = results[t.name]
            a = t.scatter_decompressed(a_arr, params)
            new_tasks[t.name] = lcstate.task_state(
                theta, lc["tasks"][t.name]["lam"], a)
        return lcstate.with_tasks(lc, new_tasks)

    def c_step(self, params, lc) -> dict:
        return self._c_step(params, lc)

    def c_step_async(self, params, lc) -> dict:
        """C step for the overlapped trainer pipeline: dispatches the
        jitted grouped solve and returns the *unblocked* state (every
        leaf a future). Unlike :meth:`c_step` it never donates its
        inputs — the caller is by construction still holding the
        previous Θ/λ/a alive inside an in-flight L step."""
        return self._c_step_async(params, lc)

    def multiplier_step_async(self, params, lc) -> dict:
        """Non-donating, non-blocking :meth:`multiplier_step` (the λ
        buffers it consumes are still referenced by the in-flight L
        step's penalty refs during overlap)."""
        return self._mult_step_async(params, lc)

    def group_summary(self, params) -> list[dict]:
        """The grouping the C step will use, from shapes only (no compute)."""
        self.resolve(params)
        xs = {t.name: jax.eval_shape(t.view.to_compressible,
                                     t.leaves(params))
              for t in self.tasks}
        # group_tasks=False runs the unsharded per-task path, so don't
        # report a layout that will never be applied (kernel dispatch
        # does apply there — solver/backend stay honest either way)
        return describe_groups(self.tasks, xs,
                               mesh=self.mesh if self.group_tasks
                               else None,
                               rules=self.sharding_rules,
                               backend=self.cstep_backend,
                               planner=self.planner)

    def _multiplier_step_impl(self, params, lc):
        mu = lc["mu"]
        new_tasks = {}
        for t in self.tasks:
            ts = lc["tasks"][t.name]
            lam = {p: ts["lam"][p]
                   - mu * (get_path(params, p).astype(jnp.float32)
                           - ts["a"][p])
                   for p in t.paths}
            if self.mesh is not None:
                # λ keeps the replicated layout init gives it: left to
                # GSPMD it follows Δ(Θ)'s, and every jitted step that
                # takes the LC state would compile a second time
                lam = _constrain_replicated(lam, self.mesh)
            new_tasks[t.name] = lcstate.task_state(ts["theta"], lam, ts["a"])
        return lcstate.with_tasks(lc, new_tasks)

    def multiplier_step(self, params, lc) -> dict:
        """λ ← λ − μ(w − Δ(Θ)) (augmented Lagrangian; skip for QP)."""
        return self._mult_step(params, lc)

    def set_mu(self, lc, mu: float, k: int) -> dict:
        # the new scalars go where the old ones live, so the jitted
        # steps see one input layout across LC steps and compile once
        return {"tasks": lc["tasks"],
                "mu": jax.device_put(jnp.float32(mu),
                                     getattr(lc["mu"], "sharding", None)),
                "k": jax.device_put(jnp.int32(k),
                                    getattr(lc["k"], "sharding", None))}

    # ------------------------------------------------------------------
    def penalty(self, params, lc) -> jnp.ndarray:
        return lc_penalty(params, lc, self.tasks)

    def _distortion_impl(self, params, lc) -> dict[str, jnp.ndarray]:
        out = {}
        for t in self.tasks:
            ts = lc["tasks"][t.name]
            d = jnp.float32(0.0)
            for p in t.paths:
                diff = get_path(params, p).astype(jnp.float32) - ts["a"][p]
                d = d + jnp.sum(diff * diff)
            out[t.name] = d
        return out

    def distortion(self, params, lc) -> dict[str, jnp.ndarray]:
        """‖w − Δ(Θ)‖² per task — must decrease across C steps (§7)."""
        return self._distortion(params, lc)

    def _shifted_distortion_impl(self, params, lc) -> dict[str, jnp.ndarray]:
        out = {}
        mu = lc["mu"]
        for t in self.tasks:
            ts = lc["tasks"][t.name]
            x = t.shifted_compressible(params, ts, mu).astype(jnp.float32)
            a = t.view.to_compressible(
                [ts["a"][p] for p in t.paths]).astype(jnp.float32)
            out[t.name] = jnp.sum((x - a) ** 2)
        return out

    def shifted_distortion(self, params, lc) -> dict[str, jnp.ndarray]:
        """‖(w − λ/μ) − Δ(Θ)‖² per task — the exact C-step objective.

        Unlike :meth:`distortion`, a warm-started C step is *guaranteed*
        not to increase this at fixed (w, λ, μ) — the paper §7 monitor
        the trainer checks around every C step.
        """
        return self._shifted_distortion(params, lc)

    def constraint_violation(self, params, lc) -> jnp.ndarray:
        """‖w − Δ(Θ)‖ over all tasks — the convergence monitor."""
        total = jnp.float32(0.0)
        for v in self.distortion(params, lc).values():
            total = total + v
        return jnp.sqrt(total)

    def compression_ratio(self, params, lc, float_bits: int = 32) -> float:
        """(uncompressed bits of compressed params) / (Θ bits)."""
        orig_bits = 0.0
        comp_bits = 0.0
        for t in self.tasks:
            ts = lc["tasks"][t.name]
            for p in t.paths:
                orig_bits += get_path(params, p).size * float_bits
            theta = ts["theta"]
            if t.view.stacked:
                # bits() can be item-dependent (RankSelection stores a
                # different rank per item), so sum per item rather than
                # extrapolating item 0 across the stack; one host
                # transfer per leaf, then index on host (no per-item
                # device round trips)
                host = jax.tree_util.tree_map(np.asarray, theta)
                n = jax.tree_util.tree_leaves(host)[0].shape[0]
                for i in range(n):
                    item = jax.tree_util.tree_map(
                        lambda x, i=i: x[i], host)
                    comp_bits += float(t.scheme.bits(item, float_bits))
            else:
                comp_bits += float(t.scheme.bits(theta, float_bits))
        return orig_bits / max(comp_bits, 1.0)

    def apply_compression(self, params):
        """w ← Δ(Θ) applied into the params pytree — the final compressed
        model (call after the LC loop; uses the latest C step of w)."""
        lc = self._last_lc
        out = params
        from repro.core.tasks import set_path
        for t in self.tasks:
            ts = lc["tasks"][t.name]
            for p in t.paths:
                leaf = get_path(params, p)
                out = set_path(out, p, ts["a"][p].astype(leaf.dtype))
        return out

    # ------------------------------------------------------------------
    def run(self, train_state, params_of: Callable, tol: float = 0.0,
            callbacks: Sequence[Callable] = ()):
        """Full LC loop (paper Fig. 2 / Listing 1).

        ``train_state`` is opaque to LC except through ``params_of``.
        ``self.l_step(train_state, lc, step) -> train_state`` runs one full
        L step (the user decides epochs/steps inside, as in the paper).
        """
        assert self.l_step is not None, "provide l_step to run()"
        params = params_of(train_state)
        lc = self.init(params)
        self._last_lc = lc
        history = []
        for k, mu in enumerate(self.mu_schedule):
            lc = self.set_mu(lc, mu, k)
            train_state = self.l_step(train_state, lc, k)
            params = params_of(train_state)
            lc = self.c_step(params, lc)
            lc = self.multiplier_step(params, lc)
            self._last_lc = lc
            m = LCMetrics(
                step=k, mu=float(mu),
                distortion={n: float(v) for n, v in
                            self.distortion(params, lc).items()},
                penalty=float(self.penalty(params, lc)),
                compression_ratio=float(
                    self.compression_ratio(params, lc)),
            )
            history.append(m)
            for cb in callbacks:
                cb(train_state, lc, m)
            if tol > 0 and float(
                    self.constraint_violation(params, lc)) < tol:
                break
        return train_state, lc, history
