"""Grouped C-step dispatch (the paper's "C steps can be run in parallel").

The per-task C step traces one scheme program per task, so HLO size and
compile time grow linearly with the task count (a per-layer config on a
large model yields dozens of structurally identical k-means/top-κ
programs). Grouped dispatch instead:

1. partitions resolved tasks by ``CompressionTask.group_signature`` —
   (scheme ``group_key()``, view item shape, dtype);
2. concatenates each group's *items* (stacked views contribute their
   stack, single-array views contribute one item) along a leading axis;
3. packs the warm-start Θ pytrees the same way (`pack_thetas`);
4. solves each group with ONE program: a **named batched kernel
   solver** resolved through ``repro.kernels.dispatch`` when the
   scheme opts in (items-grid Pallas on TPU, interpret-mode Pallas or
   the bit-identical batched jnp solver on CPU), else one ``vmap``-ed
   ``scheme.compress``;
5. slices Θ and Δ(Θ) back out per task.

Everything here runs at trace time inside the single jitted ``c_step`` —
the Python loops cost nothing at runtime, and the resulting HLO contains
one scheme program per *group* instead of per *task*.

With a ``mesh``, the packed item axis is additionally annotated with the
``"items"`` logical sharding rule (``distributed/sharding.py``, default
candidates ``[("data",), ()]``): the stacked items are embarrassingly
parallel, so GSPMD splits the group program across the data axis — a
64-layer group's C step runs data-parallel. Item counts that don't
divide the data axis are zero-padded up to the next multiple (padded
lanes are computed and discarded; items are independent, so the
surviving slices are bit-identical to the unsharded result), and the
per-task Θ/Δ(Θ) slices are re-constrained with each task's own item
count so they land where the L step consumes them. ``mesh=None``
(default) is exactly the pre-mesh path.

Kernel dispatch (``backend=``) composes with all of it: under the
batched signature, schemes that move a hyperparameter into a per-item
operand (ℓ0 pruning's κ, low-rank's target rank, rank selection's α,
k-means' valid-K count) group across values of it — one launch for
mixed-hyperparameter tasks — and the per-item operands are
padded/sharded alongside the items. Θ leaves whose *shapes* differ
across members (mixed-rank factors, mixed-K codebooks) pack with
trailing-dim padding (``pack_thetas_padded``) and slice back to each
task's own shapes after the solve. Stochastic solvers
(``scheme.wants_key``) get engine-derived per-item PRNG keys — by task
name and within-task index, identical on the grouped and per-task
paths — appended as the last operand (kernel path) or threaded as a
``key=`` kwarg (vmap path). Batched solvers that are custom-call-free
(``scheme.gspmd_safe``: the matmul-only low-rank solvers) shard under
plain GSPMD instead of the shard_map workaround. Tasks whose scheme
opts out (``group_key() is None``) fall through to the per-task path
unchanged, so exotic schemes need no vmap support; a scheme whose
subclass overrides ``compress`` without standing behind
``compress_batched`` is likewise kept on the vmap path (see
``CompressionScheme.kernel_dispatch_ready``).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.schemes.base import (
    add_leading_axis, drop_leading_axis, pack_thetas, pack_thetas_padded,
    slice_theta_like, unpack_thetas)
from repro.core.tasks import CompressionTask
from repro.distributed.sharding import (
    items_partition, shard_map, stacked_sharding)


def _task_solver(scheme, backend):
    """(solver_fn, actual_backend) for a scheme under a requested
    backend, or (None, None) → vmap path."""
    if backend in (None, "off") or not scheme.kernel_dispatch_ready():
        return None, None
    # deferred import: `import repro.core` must not eagerly pull the
    # Pallas kernel modules (jax.experimental.pallas + registration)
    # for users who never turn kernel dispatch on
    from repro.kernels.dispatch import lookup as solver_lookup
    return solver_lookup(scheme.solver, backend)


def _abstract(tree):
    """Pytree → matching ShapeDtypeStructs (works on arrays, tracers
    and ShapeDtypeStructs alike — only shape/dtype are read)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _plan_multi_group(group, xs: dict, thetas: dict, counts: list[int],
                      solver_fn, mesh, rules, backend):
    """Plan one multi-task group through the roofline cost model.

    The planner never changes *grouping*: ``solver_fn`` (resolved by
    the static rule) already fixed whether the group packs for a named
    batched solver, and the plan only re-picks the backend among the
    registered implementations of that same solver, tunes its
    items-grid tile, and decides chunking/shard_mode. Trace-safe: only
    shapes/dtypes are consulted; the optional HLO refinement lowers on
    ``ShapeDtypeStruct``s (and is skipped under a mesh). Plans are
    cached in ``repro.analysis.cost`` keyed by the group signature, so
    repeated LC boundaries — and jit-cache rebuilds — replan nothing.
    """
    from repro.analysis import cost as _cost
    from repro.kernels.dispatch import registered_backends
    t0 = group[0]
    scheme = t0.scheme
    batched = solver_fn is not None
    sig = t0.group_signature(xs[t0.name], batched=batched)
    n_items = sum(counts)
    xs_a = {t.name: _abstract(xs[t.name]) for t in group}
    th_a = {t.name: _abstract(thetas[t.name]) for t in group}
    arrays = jax.eval_shape(
        lambda xs_, th_: _pack_group(group, xs_, th_, counts,
                                     solver_fn)[0], xs_a, th_a)
    item_shape = t0.view.item_shape(xs[t0.name])
    item_elems = 1
    for d in item_shape:
        item_elems *= int(d)
    # per-row VMEM beyond the weight tile itself (codebook / threshold
    # blocks); a coarse margin is enough to rank the tile candidates
    extra_vmem = 4 * 128 * 4

    # HLO refinement only for dispatch-path groups: lowering a
    # vmap-path group traces the scheme's Python ``compress`` a second
    # time at plan time, breaking the one-trace-per-group contract —
    # and there is no named solver to re-pick for it anyway.
    lower_fn, base_fallbacks = None, ()
    if not batched:
        base_fallbacks = ("hlo-refine-skipped:vmap-path",)
    elif mesh is None:
        def lower_fn(chosen):
            lowered = lower_group(group, xs_a, th_a, mu=1.0,
                                  backend=chosen)
            return lowered.compiler_ir(dialect="hlo").as_hlo_text()

    return _cost.plan_group(
        sig, n_items, arrays, (arrays[1], arrays[0]),
        requested_backend=str(backend) if backend is not None else "off",
        solver=scheme.solver if batched else None,
        registered=registered_backends(scheme.solver if batched
                                       else None),
        gspmd_safe=bool(batched and scheme.gspmd_safe), mesh=mesh,
        item_elems=item_elems, extra_vmem_per_row=extra_vmem,
        lower_fn=lower_fn, base_fallbacks=base_fallbacks)


def _apply_plan(scheme, solver_fn, plan):
    """Re-resolve the group's solver under the planner's choices.

    Only swaps among registered implementations of the *same* solver
    (backend + tile); a vmap-path group (``solver_fn is None``) stays
    on vmap — the plan never flips the grouping identity.
    """
    if plan is None or solver_fn is None:
        return solver_fn
    from repro.kernels.dispatch import lookup as solver_lookup
    fn, _ = solver_lookup(scheme.solver, plan.backend,
                          tile=plan.block_rows)
    return fn if fn is not None else solver_fn


def build_groups(tasks: Sequence[CompressionTask], xs: dict,
                 backend: str | None = None,
                 for_init: bool = False) -> list[list[CompressionTask]]:
    """Partition tasks into groups of equal group signature.

    ``xs`` maps task name → compressible array (or ShapeDtypeStruct).
    Non-groupable tasks come back as singleton groups. Group order
    follows first appearance, so the output is deterministic. With a
    kernel ``backend`` active, dispatch-ready schemes group by their
    ``batch_key()`` (κ and friends become per-item operands) — but only
    when the named solver actually *resolves* in the registry: an
    unregistered name must keep the legacy per-value grouping, or the
    vmap fallback would solve a mixed-hyperparameter group with
    ``group[0]``'s values.
    """
    groups: dict = {}
    order: list = []
    solos: list[list[CompressionTask]] = []
    for t in tasks:
        batched = _task_solver(t.scheme, backend)[0] is not None
        sig = t.group_signature(xs[t.name], batched=batched)
        if for_init and sig is not None:
            # init-only hyperparameters (a DP warm start) are invisible
            # to group_key; the init grouping identity must include them
            ik = t.scheme.init_key()
            sig = None if ik is None else (sig, ik)
        if sig is None:
            solos.append([t])
            continue
        if sig not in groups:
            groups[sig] = []
            order.append(sig)
        groups[sig].append(t)
    return [groups[s] for s in order] + solos


def describe_groups(tasks: Sequence[CompressionTask], xs: dict,
                    mesh: Mesh | None = None,
                    rules: dict | None = None,
                    backend: str | None = None,
                    planner: str | None = None) -> list[dict]:
    """Human/bench-readable summary of the grouping a C step would use.

    With a ``mesh``, each entry also reports how the packed item axis
    would be laid out: ``spec`` is the PartitionSpec of the stacked
    leading axis (``None`` whenever the axis is not sharded — no mesh,
    per-task path, or replication fallback) and ``padding`` is the
    number of zero items appended so the count divides the assigned
    mesh axes (0 when it already divides, or when not sharded).

    ``solver``/``backend`` report kernel dispatch *honestly*: ``solver``
    is the registry name the group's solve will actually go through
    (``None`` = vmapped scheme program) and ``backend`` the resolved
    implementation that will run — e.g. a ``"pallas"`` request off-TPU
    reports ``"interpret"``.

    ``planner="on"`` additionally attaches each multi-task group's
    :class:`repro.analysis.cost.GroupPlan` as a ``plan`` dict (modeled
    roofline terms, chosen backend/tile/chunks/shard_mode, recorded
    fallbacks) — the same cached plan the C step will use, with Θ
    shapes staged via ``jax.eval_shape`` of the scheme init (nothing
    executes). When planned, ``backend`` reports the planner's choice.
    """
    out = []
    for group in build_groups(tasks, xs, backend=backend):
        t0 = group[0]
        sig = t0.group_signature(xs[t0.name])
        grouped = sig is not None and len(group) > 1
        n_items = sum(t.view.item_count(xs[t.name]) for t in group)
        spec, pad = None, 0
        if mesh is not None and grouped:
            entry, pad = items_partition(n_items, mesh, rules)
            spec = P(entry) if entry is not None else None
        solver_fn, actual = _task_solver(t0.scheme, backend)
        shard_mode = None
        if spec is not None:
            # matmul-only solvers (scheme.gspmd_safe) shard under plain
            # GSPMD; everything else keeps the shard_map custom-call
            # workaround (docs/architecture.md)
            shard_mode = ("gspmd" if solver_fn is not None
                          and t0.scheme.gspmd_safe else "shard_map")
        plan_dict = None
        if planner == "on" and grouped:
            counts = [t.view.item_count(xs[t.name]) for t in group]
            thetas = {t.name: jax.eval_shape(t.scheme_init, xs[t.name])
                      for t in group}
            plan = _plan_multi_group(group, xs, thetas, counts,
                                     solver_fn, mesh, rules, backend)
            plan_dict = plan.as_dict()
            if solver_fn is not None:
                actual = plan.backend
        out.append({
            "scheme": t0.scheme.name,
            "item_shape": t0.view.item_shape(xs[t0.name]),
            "tasks": [t.name for t in group],
            "items": n_items,
            # singleton groups run the per-task path even when groupable
            "grouped": grouped,
            "spec": spec,
            "padding": pad,
            "shard_mode": shard_mode,
            "solver": t0.scheme.solver if solver_fn is not None else None,
            "backend": actual,
            "plan": plan_dict,
        })
    return out


def _pad_leading(x, pad: int):
    """Append ``pad`` zero items along axis 0 (the packed item axis)."""
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)


def _constrain_leading(tree, mesh, entry):
    """with_sharding_constraint splitting only the leading axis."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(
            x, stacked_sharding(mesh, entry, x.ndim)), tree)


def _constrain_replicated(tree, mesh):
    """with_sharding_constraint pinning every leaf fully replicated."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P())), tree)


def _chunk_bounds(n_items: int, n_chunks: int) -> list[tuple[int, int]]:
    """Contiguous item-axis slices splitting ``n_items`` into
    ``n_chunks`` near-equal launches (first chunks take the remainder)."""
    n_chunks = max(1, min(int(n_chunks), n_items))
    base, rem = divmod(n_items, n_chunks)
    bounds, lo = [], 0
    for i in range(n_chunks):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _run_group_solve(solve, arrays: tuple, n_items: int,
                     mesh: Mesh | None, rules: dict | None,
                     gspmd: bool = False, n_chunks: int = 1):
    """Run a packed-group solve, optionally sharded over the mesh.

    ``arrays`` are pytrees whose every leaf carries the packed item
    axis; ``solve(*arrays)`` must return a 2-tuple of such pytrees
    (new Θ, decompressed items). Handles the pad → replicate-constrain
    → shard_map → slice dance from the module docstring; ``mesh=None``
    calls ``solve`` directly. Returns ``(theta_packed, a_packed)`` with
    the padding already sliced off.

    ``gspmd=True`` (matmul-only batched solvers — ``scheme.gspmd_safe``)
    bypasses the shard_map workaround: the packed item axis is annotated
    with plain sharding constraints and GSPMD partitions the solve
    itself. Correct only when every op in ``solve`` has an SPMD rule
    (no LAPACK custom calls); padded lanes are still independent items
    computed and discarded.

    ``n_chunks > 1`` (planner-chosen when the packed working set blows
    the VMEM/HBM budget) splits the *unsharded* solve into several
    launches over contiguous item slices and re-concatenates Θ exactly.
    Bit-identical to the single launch: packing (incl. the group-wide
    trailing-dim padding) happened before the split and every batched
    solver is per-item independent. Sharded groups never chunk here —
    the planner records the ``chunking-disabled-under-mesh`` fallback
    instead.
    """
    entry, pad = (None, 0)
    if mesh is not None:
        entry, pad = items_partition(n_items, mesh, rules)

    if entry is not None:
        # padded lanes are independent items computed and discarded, so
        # the surviving slices match mesh=None exactly
        if pad:
            arrays = tuple(
                jax.tree_util.tree_map(lambda x: _pad_leading(x, pad), a)
                for a in arrays)
        if gspmd:
            # plain GSPMD: constrain the packed item axis sharded on the
            # way in and out and let the partitioner split the batched
            # matmuls — no manual region, no custom-call workaround
            arrays = tuple(_constrain_leading(a, mesh, entry)
                           for a in arrays)
            theta_packed, a_packed = solve(*arrays)
            theta_packed = _constrain_leading(theta_packed, mesh, entry)
            a_packed = _constrain_leading(a_packed, mesh, entry)
        else:
            # enter the shard_map boundary from an explicit replicated
            # layout: on jax 0.4.x GSPMD's reshard-into-manual from a
            # dim-sharded concatenate miscompiles (the output comes back
            # psummed over the unmentioned mesh axes), while
            # replicated → manual slices correctly.
            arrays = tuple(_constrain_replicated(a, mesh) for a in arrays)
            # shard_map, not bare GSPMD: each device solves its local
            # items, so schemes built on custom calls (LAPACK svd/qr)
            # partition correctly — the SPMD partitioner has no rule for
            # those and miscompiles sliced uses.
            spec = P(entry)
            theta_packed, a_packed = shard_map(
                solve, mesh, in_specs=(spec,) * len(arrays),
                out_specs=(spec, spec))(*arrays)
    elif n_chunks > 1 and n_items > 1:
        parts = []
        for lo, hi in _chunk_bounds(n_items, n_chunks):
            chunk = tuple(
                jax.tree_util.tree_map(lambda x: x[lo:hi], a)
                for a in arrays)
            parts.append(solve(*chunk))
        theta_packed = jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(leaves, axis=0),
            *[p[0] for p in parts])
        a_packed = jnp.concatenate([p[1] for p in parts], axis=0)
    else:
        theta_packed, a_packed = solve(*arrays)

    if pad:
        theta_packed = jax.tree_util.tree_map(
            lambda x: x[:n_items], theta_packed)
        a_packed = a_packed[:n_items]
    return theta_packed, a_packed


def _packed_keys(group: Sequence[CompressionTask], counts: list[int]):
    """One (Σ items, 2) uint32 key array for a ``wants_key`` group.

    The single source of key packing for every grouped path (solver
    operands, vmap fallback, grouped init) — ``CompressionTask
    .item_keys`` derives each slice from task name + within-task index,
    so all paths see identical per-item keys."""
    return jnp.concatenate([t.item_keys(n) for t, n in zip(group, counts)],
                           axis=0)


def _group_operands(group: Sequence[CompressionTask], counts: list[int]):
    """Concatenate each task's per-item solver operands into the packed
    form ``compress_batched`` consumes (mixed-κ: one (Σ items,) array).
    Schemes with ``wants_key`` get their packed per-item PRNG keys
    appended as the LAST operand."""
    per_task = [t.scheme.batch_operands(n) for t, n in zip(group, counts)]
    operands = tuple(jnp.concatenate(parts, axis=0)
                     for parts in zip(*per_task))
    if group[0].scheme.wants_key:
        operands = operands + (_packed_keys(group, counts),)
    return operands


def _group_solve(scheme, solver_fn, mu):
    """The packed-group solve callable — one body shared by the
    executing path (:func:`grouped_compress`) and the lowering path
    (:func:`lower_group`), so what the linter inspects is exactly what
    the C step runs. ``solve(items, packed_theta, *operands) →
    (new_theta, decompressed items)``."""
    def _solve(xi, ti, *ops):
        if solver_fn is not None:
            nt = scheme.compress_batched(solver_fn, xi, ti, ops, mu=mu)
        elif scheme.wants_key:
            (keys,) = ops
            nt = jax.vmap(
                lambda x, th, k: scheme.compress(x, th, mu=mu,
                                                 key=k))(xi, ti, keys)
        else:
            nt = jax.vmap(
                lambda x, th: scheme.compress(x, th, mu=mu))(xi, ti)
        return nt, jax.vmap(scheme.decompress)(nt)

    return _solve


def _pack_group(group: Sequence[CompressionTask], xs: dict, thetas: dict,
                counts: list[int], solver_fn):
    """Build the packed array tuple a group solve consumes.

    Returns ``(arrays, thetas_lead)``: ``arrays`` is ``(items,
    packed_theta, *operands)`` and ``thetas_lead`` the per-task Θs with
    a leading item axis (the slice-back templates). Pure tracing code —
    runs concretely inside the jitted C step and abstractly under
    ``jax.eval_shape`` when lowering."""
    scheme = group[0].scheme
    items = jnp.concatenate(
        [t.view.to_items(xs[t.name]) for t in group], axis=0)
    thetas_lead = [thetas[t.name] if t.view.stacked
                   else add_leading_axis(thetas[t.name])
                   for t in group]
    if solver_fn is not None:
        # batched solvers take Θ leaves padded to the group max
        # trailing shape (mixed-rank factors → R_max, mixed-K
        # codebooks → K_max); the vmap path never mixes shapes
        # (they are part of its grouping identity)
        packed = pack_thetas_padded(thetas_lead)
        operands = _group_operands(group, counts)
    else:
        packed = pack_thetas(thetas_lead)
        operands = ((_packed_keys(group, counts),)
                    if scheme.wants_key else ())
    return (items, packed) + operands, thetas_lead


def lower_group(group: Sequence[CompressionTask], xs: dict, thetas: dict,
                mu: float = 1.0, mesh: Mesh | None = None,
                rules: dict | None = None, backend: str | None = None,
                donate: bool = False, plan=None):
    """Lower one group's packed C solve to HLO **without executing it**.

    The static-analysis hook behind ``repro.analysis.lint``'s HLO layer:
    it stages exactly the program :func:`grouped_compress` would run for
    ``group`` — same packing, same solver resolution, same
    mesh/shard-mode logic — through ``jax.jit(...).lower`` on
    ``ShapeDtypeStruct``s, and returns the ``Lowered`` object (use
    ``.as_text()`` / ``.compiler_ir(dialect="hlo")``).

    ``xs``/``thetas`` may hold real arrays or ``ShapeDtypeStruct``s —
    nothing is materialized either way. ``donate=True`` marks the packed
    Θ input donated, mirroring the engine's donated LC state, so a
    donation-aliasing check sees the engine's buffer story. A singleton
    group lowers the same packed program with one item.

    ``plan`` (a :class:`repro.analysis.cost.GroupPlan`) stages the
    *planner-chosen* program instead — backend/tile re-resolved through
    :func:`_apply_plan` and the chunked launch structure included — so
    the Layer-3 lint rules see exactly what a planner-on C step runs.
    """
    scheme = group[0].scheme
    solver_fn, _ = _task_solver(scheme, backend)
    n_chunks = 1
    if plan is not None:
        n_chunks = plan.n_chunks
        solver_fn = _apply_plan(scheme, solver_fn, plan)
    counts = [t.view.item_count(xs[t.name]) for t in group]
    n_items = sum(counts)

    arrays = jax.eval_shape(
        lambda xs_, thetas_: _pack_group(group, xs_, thetas_, counts,
                                         solver_fn)[0],
        xs, {t.name: thetas[t.name] for t in group})

    solve = _group_solve(scheme, solver_fn, mu)
    gspmd = solver_fn is not None and scheme.gspmd_safe

    def run(items, packed, *ops):
        return _run_group_solve(solve, (items, packed) + ops, n_items,
                                mesh, rules, gspmd=gspmd,
                                n_chunks=n_chunks)

    jitted = jax.jit(run, donate_argnums=(1,) if donate else ())
    return jitted.lower(*arrays)


def compile_group(group: Sequence[CompressionTask], xs: dict,
                  thetas: dict, mesh: Mesh | None = None,
                  rules: dict | None = None, backend: str | None = None,
                  plan=None):
    """AOT-compile one group's packed C solve, cached across boundaries.

    The executable half of the planner cache: μ rides as the FIRST
    traced argument (not baked into the trace like the jitted engine
    path), so ONE compile serves every LC boundary — call the returned
    executable as ``compiled(jnp.float32(mu), *arrays)`` and it returns
    ``(packed_theta, packed_items)``. Executables are cached in
    ``repro.analysis.cost`` keyed by the same group signature as plans;
    repeated boundaries (and jit-cache rebuilds) pay zero
    re-lower/re-trace — ``cost.cache_stats()`` proves it and
    ``bench_roofline`` / the Layer-3 lint hard-assert it.

    ``xs``/``thetas`` must hold concrete arrays (packing runs eagerly).
    Returns ``(compiled, arrays)``.
    """
    from repro.analysis import cost as _cost
    t0 = group[0]
    scheme = t0.scheme
    solver_fn, _ = _task_solver(scheme, backend)
    n_chunks = 1
    if plan is not None:
        n_chunks = plan.n_chunks
        solver_fn = _apply_plan(scheme, solver_fn, plan)
    batched = solver_fn is not None
    sig = t0.group_signature(xs[t0.name], batched=batched)
    counts = [t.view.item_count(xs[t.name]) for t in group]
    n_items = sum(counts)
    arrays = _pack_group(group, xs, thetas, counts, solver_fn)[0]
    gspmd = batched and scheme.gspmd_safe

    def run(mu, items, packed, *ops):
        solve = _group_solve(scheme, solver_fn, mu)
        return _run_group_solve(solve, (items, packed) + ops, n_items,
                                mesh, rules, gspmd=gspmd,
                                n_chunks=n_chunks)

    key = ("exec",) + _cost.plan_key(sig, n_items, arrays, mesh,
                                     str(backend))

    def build():
        mu_sds = jax.ShapeDtypeStruct((), jnp.float32)
        arrays_sds = _abstract(arrays)
        return jax.jit(run).lower(mu_sds, *arrays_sds).compile()

    return _cost.get_executable(key, build), arrays


def solve_task(task: CompressionTask, x, theta, mu,
               backend: str | None = None):
    """One task's C solve, kernel-dispatched when the scheme opts in.

    The per-task twin of the grouped batched path: the same named
    solver runs on the task's own item stack (a single-array view is a
    1-item stack), so ``group_tasks=False`` and singleton groups also
    exercise the kernel path. Falls back to the plain (vmapped when
    stacked) ``scheme.compress``.
    """
    solver_fn, _ = _task_solver(task.scheme, backend)
    if solver_fn is None:
        return task.scheme_compress(x, theta, mu)
    items = task.view.to_items(x)
    ti = theta if task.view.stacked else add_leading_axis(theta)
    n_items = task.view.item_count(x)
    operands = task.scheme.batch_operands(n_items)
    if task.scheme.wants_key:
        operands = operands + (task.item_keys(n_items),)
    nt = task.scheme.compress_batched(solver_fn, items, ti, operands,
                                      mu=mu)
    return nt if task.view.stacked else drop_leading_axis(nt)


def grouped_compress(tasks: Sequence[CompressionTask], xs: dict,
                     thetas: dict, mu, mesh: Mesh | None = None,
                     rules: dict | None = None,
                     backend: str | None = None,
                     planner: str | None = None) -> dict:
    """One C step over all tasks with grouped dispatch.

    Returns ``{task_name: (new_theta, a_arr)}`` where ``a_arr`` is the
    decompressed Δ(Θ) in the task's compressible shape. Must be called
    under jit (it is trace-time machinery, not a runtime scheduler).
    With a ``mesh``, the packed item axis of every multi-task group is
    sharded per the ``"items"`` rule — see the module docstring; the
    numerics are unchanged. With a kernel ``backend``, opted-in schemes
    solve through the dispatch layer's named batched solvers.

    ``planner="on"`` routes every multi-task group through the roofline
    cost model (``repro.analysis.cost``): backend re-picked among the
    solver's registered implementations, Pallas tile rows tuned (TPU
    only), oversized groups chunked into several launches. Results are
    bit-identical to ``planner=None`` by construction — off-TPU the
    planner resolves exactly the static rule and chunked solves
    re-concatenate per-item-independent Θ exactly; plans are cached so
    repeated boundaries replan nothing.
    """
    out = {}
    for group in build_groups(tasks, xs, backend=backend):
        if len(group) == 1:
            # singleton: per-task path (also the non-groupable
            # fallback) — kernel-dispatched when the scheme opts in,
            # but never sharded (nothing to split across tasks).
            t = group[0]

            def solve(x, th, mu_, t=t):
                return solve_task(t, x, th, mu_, backend=backend)

            if mesh is not None and mesh.devices.size > 1 and \
                    _task_solver(t.scheme, backend)[1] in \
                    ("pallas", "interpret"):
                # GSPMD cannot partition a Pallas (Mosaic) call, even on
                # replicated operands: every device solves the whole task
                # inside a manual region instead. Other solvers stay
                # with GSPMD, which may split them.
                solve = shard_map(solve, mesh, in_specs=(P(), P(), P()),
                                  out_specs=P())
            theta = solve(xs[t.name], thetas[t.name], mu)
            out[t.name] = (theta, t.scheme_decompress(theta))
            continue

        # equal batched signature ⇒ same class and batch_key; operand-
        # ized hyperparameters (κ) may differ per member and ride in
        # packed per-item arrays, never through group[0]'s attributes
        scheme = group[0].scheme
        solver_fn, _ = _task_solver(scheme, backend)
        counts = [t.view.item_count(xs[t.name]) for t in group]
        n_items = sum(counts)
        n_chunks = 1
        if planner == "on":
            plan = _plan_multi_group(group, xs, thetas, counts,
                                     solver_fn, mesh, rules, backend)
            n_chunks = plan.n_chunks
            solver_fn = _apply_plan(scheme, solver_fn, plan)
        arrays, thetas_lead = _pack_group(group, xs, thetas, counts,
                                          solver_fn)

        new_packed, a_packed = _run_group_solve(
            _group_solve(scheme, solver_fn, mu), arrays, n_items, mesh,
            rules, gspmd=solver_fn is not None and scheme.gspmd_safe,
            n_chunks=n_chunks)

        theta_parts = unpack_thetas(new_packed, counts)
        if solver_fn is not None:
            # trailing-dim padding back off: every task's Θ lands in
            # its own LC-state shapes (live entries lead — see
            # pack_thetas_padded)
            theta_parts = [slice_theta_like(th, old) for th, old
                           in zip(theta_parts, thetas_lead)]
        off = 0
        for t, th, n in zip(group, theta_parts, counts):
            a_arr = t.view.from_items(a_packed[off:off + n])
            off += n
            if not t.view.stacked:
                th = drop_leading_axis(th)
            elif mesh is not None:
                # land the sliced stack where the L step consumes it:
                # the task's own item count decides its spec (exact
                # divisibility only — slices can't be padded)
                t_entry, _ = items_partition(n, mesh, rules,
                                             allow_pad=False)
                if t_entry is not None:
                    th = _constrain_leading(th, mesh, t_entry)
                    a_arr = _constrain_leading(a_arr, mesh, t_entry)
            out[t.name] = (th, a_arr)
    return out


def grouped_init(tasks: Sequence[CompressionTask], xs: dict,
                 mesh: Mesh | None = None,
                 rules: dict | None = None) -> dict:
    """Direct compression Θ^DC = Π(w̄) with grouped dispatch.

    The cold-start twin of :func:`grouped_compress`: tasks group by
    their (non-batched) signature extended with ``scheme.init_key()``
    — ``init`` has no warm start to feed a kernel solver, operand-ized
    hyperparameters like κ are still static here, and init-only
    settings (DP warm starts) must not merge — so each group runs ONE
    vmapped ``scheme.init``, and compile cost at startup is O(groups)
    instead of O(tasks). Returns
    ``{task_name: (theta, a_arr)}``; call under jit. With a ``mesh``
    the packed item axis shards exactly like the C step's.
    """
    out = {}
    for group in build_groups(tasks, xs, for_init=True):
        if len(group) == 1:
            t = group[0]
            theta = t.scheme_init(xs[t.name])
            out[t.name] = (theta, t.scheme_decompress(theta))
            continue

        scheme = group[0].scheme  # identical init_key ⇒ same static cfg
        items = jnp.concatenate(
            [t.view.to_items(xs[t.name]) for t in group], axis=0)
        counts = [t.view.item_count(xs[t.name]) for t in group]
        n_items = sum(counts)

        if scheme.wants_key:
            keys = _packed_keys(group, counts)

            def _solve(xi, ki, scheme=scheme):
                th = jax.vmap(lambda x, k: scheme.init(x, key=k))(xi, ki)
                return th, jax.vmap(scheme.decompress)(th)

            arrays = (items, keys)
        else:
            def _solve(xi, scheme=scheme):
                th = jax.vmap(lambda x: scheme.init(x))(xi)
                return th, jax.vmap(scheme.decompress)(th)

            arrays = (items,)

        theta_packed, a_packed = _run_group_solve(
            _solve, arrays, n_items, mesh, rules)

        theta_parts = unpack_thetas(theta_packed, counts)
        off = 0
        for t, th, n in zip(group, theta_parts, counts):
            a_arr = t.view.from_items(a_packed[off:off + n])
            off += n
            if not t.view.stacked:
                th = drop_leading_axis(th)
            elif mesh is not None:
                t_entry, _ = items_partition(n, mesh, rules,
                                             allow_pad=False)
                if t_entry is not None:
                    th = _constrain_leading(th, mesh, t_entry)
                    a_arr = _constrain_leading(a_arr, mesh, t_entry)
            out[t.name] = (th, a_arr)
    return out
