"""LC state pytrees.

``LCState`` travels with the train state through jit boundaries and
checkpoints:

    {"tasks": {task_name: {"theta": <scheme pytree>,
                           "lam":   {param_path: array},   # multipliers
                           "a":     {param_path: array}}}, # a = Δ(Θ) scattered
     "mu": f32 scalar,
     "k":  i32 LC-step counter}

``a`` (the decompressed target) and ``lam`` are stored *per original
parameter leaf* — because the L2 penalty separates over leaves, the L step
never materializes the concatenated view, and both arrays inherit the
parameter's sharding.

Donation contract: ``LCAlgorithm``'s synchronous C/multiplier steps may
donate the incoming state's buffers (Θ/λ/a update in place on
accelerators). The *async* entry points used by the trainer's overlapped
pipeline never donate — during overlap the previous state's λ/a leaves
are still read by the in-flight L step, so both generations of buffers
must stay live until the trainer swaps its penalty refs
(:func:`ready_probe` is how it polls the in-flight generation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def task_state(theta, lam: dict, a: dict) -> dict:
    return {"theta": theta, "lam": lam, "a": a}


def lc_state(tasks: dict, mu: float, k: int = 0) -> dict:
    return {"tasks": tasks, "mu": jnp.float32(mu), "k": jnp.int32(k)}


def with_tasks(lc: dict, new_tasks: dict) -> dict:
    """New LC state with ``tasks`` replaced, μ/k carried through — the
    one-liner every C/multiplier step ends with (keeps the pytree layout
    identical across the grouped and per-task paths, so checkpoints and
    the trainer's penalty refs never notice which engine produced it)."""
    return {"tasks": new_tasks, "mu": lc["mu"], "k": lc["k"]}


def zeros_like_leaves(paths: list[str], leaves: list) -> dict:
    return {p: jnp.zeros(l.shape, jnp.float32)
            for p, l in zip(paths, leaves)}


def ready_probe(lc: dict):
    """One representative leaf of an in-flight LC state, for non-blocking
    readiness polling (``probe.is_ready()``) in the overlapped trainer.

    The last task leaf in tree order is chosen: the multiplier step's λ
    updates are the final work dispatched at an LC boundary, so when this
    leaf lands the whole C+λ chain is (to within dispatch-order slack)
    done.
    """
    return jax.tree_util.tree_leaves(lc["tasks"])[-1]

