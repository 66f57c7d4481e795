"""Kernel dispatch layer: named batched C-step solvers, per backend.

The paper's decoupling claim — the C step is a swappable signal-
compression subroutine — only stays free if the *implementation* of a
solve can change underneath a scheme without the scheme (or the grouped
engine, or the trainer) noticing. This registry is that seam:

* a scheme declares a **solver name** (``CompressionScheme.solver``,
  e.g. ``"kmeans_lloyd"``, ``"topk_mask"``) and implements
  ``compress_batched`` against the solver's calling convention;
* the grouped C step (``core/grouping.py``) resolves the name to a
  concrete implementation **per backend** at trace time:

  ============  =====================================================
  backend       implementation
  ============  =====================================================
  ``pallas``    batched items-grid Pallas kernel, compiled (TPU)
  ``interpret`` the same Pallas kernel, ``interpret=True`` (CPU/CI —
                exercises the kernel path without a TPU)
  ``jnp``       pure-jnp batched solver, bit-identical to the legacy
                vmapped scheme program
  ============  =====================================================

* requests are resolved honestly: ``"auto"`` picks ``pallas`` on TPU
  and ``jnp`` elsewhere; an explicit ``"pallas"`` off-TPU falls back to
  ``interpret`` (the kernel still runs, slowly) rather than silently
  switching algorithms; unknown solver names resolve to ``(None,
  None)`` so callers fall back to the vmap path and
  ``describe_groups`` reports what actually ran.

Solver calling conventions (all arrays carry the packed leading item
axis ``I``):

* ``kmeans_lloyd(w (I,P) f32, codebooks0 (I,K_max) f32,
  kvalid (I,) i32, *, iters) -> (codebooks (I,K_max) f32,
  assign (I,P) i32)`` — codebooks are padded to the group-wide
  ``K_max``; ``kvalid`` is the traced per-item live-entry count, so
  tasks differing only in K share one launch (mixed-K grouping).
* ``topk_mask(w (I,P) f32, kappa (I,) i32) -> theta (I,P) f32`` —
  κ is a *traced per-item operand*, which is what lets tasks that
  differ only in κ share one kernel launch (mixed-κ grouping).
* ``project_l1_ball(w (I,P) f32, radius (I,) f32) -> theta (I,P)
  f32`` — per-item ℓ1-ball projection, one sort+cumsum over the item
  axis (mixed-radius grouping).
* ``soft_threshold(w (I,P) f32, alpha (I,) f32, mu) -> theta (I,P)
  f32`` — the ℓ1-penalty prox at α_i/μ (mixed-α grouping).
* ``lowrank_rsvd(w (I,m,n) f32, rank (I,) i32, keys (I,2) u32, *,
  r_max) -> (u (I,m,r_max), v (I,n,r_max))`` — batched randomized
  SVD, matmul-only (``kernels/lowrank``); factors pre-scaled by √s
  and masked to each item's rank, padded to the static group ``r_max``
  (mixed-rank grouping). ``keys`` are the engine-appended per-item
  sketch keys (``CompressionScheme.wants_key``).
* ``rank_select(w (I,m,n) f32, alpha (I,) f32, keys (I,2) u32, mu, *,
  r_max, cost) -> (u, v, rank (I,) i32)`` — batched automatic rank
  selection over the same spectrum (mixed-α grouping).

The matmul-only solvers (``lowrank_rsvd``, ``rank_select``,
``project_l1_ball``, ``soft_threshold``) register a ``jnp``
implementation only — they contain no Pallas kernel and no LAPACK
custom call; ``interpret``/``pallas`` requests fall back to the same
batched jnp program via the registry's backend-gap rule.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax

BACKENDS = ("jnp", "interpret", "pallas")
#: user-facing request values (TrainerConfig.cstep_backend etc.)
REQUESTS = ("auto", "jnp", "interpret", "pallas", "off")

_REGISTRY: dict[str, dict[str, Callable]] = {}

#: solvers whose kernel path takes a planner-tunable items-grid tile
#: (``block_rows=`` kwarg on the registered implementation). The group
#: planner (``analysis/cost``) only offers tile choices for these.
TILED_SOLVERS: dict[str, str] = {
    "kmeans_lloyd": "block_rows",
    "topk_mask": "block_rows",
}


def register(solver: str, backend: str, fn: Callable) -> None:
    """Register ``fn`` as the ``backend`` implementation of ``solver``."""
    assert backend in BACKENDS, backend
    _REGISTRY.setdefault(solver, {})[backend] = fn


def registered_backends(solver: str | None) -> tuple[str, ...]:
    """Backends actually carrying ``solver`` (planner input)."""
    if solver is None or solver not in _REGISTRY:
        return ()
    return tuple(sorted(_REGISTRY[solver]))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(requested: str | None = "auto") -> str | None:
    """Requested backend → the backend that will actually run.

    ``None``/``"off"`` disables kernel dispatch entirely (pure vmapped
    scheme programs, κ static). ``"auto"`` is ``pallas`` on TPU and
    ``jnp`` elsewhere. ``"pallas"`` without a TPU degrades to
    ``interpret`` — the kernel path, emulated — so CPU tests and CI
    exercise the same program the TPU compiles; on a TPU it is always
    the compiled kernel.
    """
    if requested is None or requested == "off":
        return None
    if requested not in REQUESTS:
        raise ValueError(
            f"cstep backend must be one of {REQUESTS}, got {requested!r}")
    if requested == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if requested == "pallas" and not _on_tpu():
        return "interpret"
    return requested


def lookup(solver: str | None,
           requested: str | None = "auto",
           tile: int | None = None) -> tuple[Callable | None,
                                             str | None]:
    """(implementation, actual backend) for a solver name, or
    ``(None, None)`` when dispatch is off / the name is unregistered —
    the caller then uses its vmap fallback. A backend gap (name known,
    backend missing) falls back to the registered ``jnp`` solver so the
    result is still batched.

    ``tile`` (planner-chosen ``block_rows``) is bound onto the
    implementation when the solver is tile-parameterized
    (:data:`TILED_SOLVERS`) and the resolved backend runs the kernel
    path; the jnp implementations ignore tiles by construction.
    """
    backend = resolve_backend(requested)
    if backend is None or solver is None or solver not in _REGISTRY:
        return None, None
    impls = _REGISTRY[solver]
    if backend not in impls:
        if "jnp" in impls:
            return impls["jnp"], "jnp"
        return None, None
    fn = impls[backend]
    if tile is not None and backend in ("pallas", "interpret") and \
            solver in TILED_SOLVERS:
        fn = partial(fn, **{TILED_SOLVERS[solver]: int(tile)})
    return fn, backend


def solver_table() -> dict[str, tuple[str, ...]]:
    """{solver name: registered backends} — for docs and diagnostics."""
    return {name: tuple(sorted(impls)) for name, impls in
            sorted(_REGISTRY.items())}


def registry_entries() -> dict[str, dict[str, Callable]]:
    """Shallow copy of the raw registry: {solver: {backend: impl}}.

    Metadata accessor for tooling (``repro.analysis.lint`` contract
    layer) — callers must treat the inner callables as opaque; use
    :func:`lookup` for dispatch so the honest-fallback rules apply.
    """
    return {name: dict(impls) for name, impls in _REGISTRY.items()}


def solver_signature(solver: str,
                     backend: str = "jnp") -> tuple[str, ...] | None:
    """Positional parameter names of a registered solver implementation
    (keyword-only config like ``iters``/``r_max`` excluded), unwrapping
    ``functools.partial``. ``None`` when the (solver, backend) entry is
    missing or the underlying callable is not introspectable.

    This is the machine-readable half of the calling conventions in the
    module docstring — the lint contract layer checks each scheme's
    declared ``solver_operands`` against it.
    """
    import inspect

    impls = _REGISTRY.get(solver, {})
    fn = impls.get(backend)
    if fn is None:
        return None
    while isinstance(fn, partial):
        fn = fn.func
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return None
    return tuple(
        p.name for p in sig.parameters.values()
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))


# ----------------------------------------------------------------------
# built-in solvers (import at the bottom: ops modules must exist before
# registration, and this module must define lookup() before core code
# importing it mid-cycle resolves anything)
# ----------------------------------------------------------------------
from repro.kernels.kmeans import ops as _kops    # noqa: E402
from repro.kernels.prune import ops as _pops     # noqa: E402

register("kmeans_lloyd", "jnp", partial(_kops.kmeans_batched, impl="jnp"))
register("kmeans_lloyd", "interpret",
         partial(_kops.kmeans_batched, impl="interpret"))
register("kmeans_lloyd", "pallas",
         partial(_kops.kmeans_batched, impl="pallas"))

register("topk_mask", "jnp", partial(_pops.topk_mask_batched, impl="jnp"))
register("topk_mask", "interpret",
         partial(_pops.topk_mask_batched, impl="interpret"))
register("topk_mask", "pallas",
         partial(_pops.topk_mask_batched, impl="pallas"))

# matmul-only solvers: jnp registration only (no kernel to emulate; the
# backend-gap rule serves interpret/pallas requests the same program)
from repro.kernels.lowrank import ops as _lops  # noqa: E402

register("lowrank_rsvd", "jnp", _lops.lowrank_rsvd_batched)
register("rank_select", "jnp", _lops.rank_select_batched)
register("project_l1_ball", "jnp", _pops.project_l1_ball_batched)
register("soft_threshold", "jnp", _pops.soft_threshold_batched)
