"""Jit'd public wrappers for the k-means C-step kernel.

``use_pallas="auto"`` runs the Pallas kernel in interpret mode on CPU
(for validation) and compiled on TPU; the jnp reference path produces
identical results and is what the GSPMD-sharded C step uses when the
weight vector is distributed (the kernel is a per-shard building block).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.kmeans import ref
from repro.kernels.kmeans.kmeans import (
    LANES, ROWS, kmeans_assign_moments, kmeans_assign_moments_batched)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def assign_moments(w: jnp.ndarray, codebook: jnp.ndarray,
                   use_pallas: bool | str = "auto"):
    """Nearest-centroid assignment + cluster moments; pads internally."""
    if use_pallas == "auto":
        use_pallas = _on_tpu()
    if not use_pallas:
        return ref.kmeans_assign_moments_ref(w, codebook)
    p = w.shape[0]
    tile = ROWS * LANES
    pad = (-p) % tile
    if pad:
        # pad with +inf-distance sentinel: clone of codebook[0] so padded
        # elements land in cluster 0; subtract them from the moments after
        wp = jnp.concatenate([w, jnp.full((pad,), codebook[0], w.dtype)])
    else:
        wp = w
    assign, sums, counts = kmeans_assign_moments(
        wp, codebook, interpret=not _on_tpu())
    if pad:
        sums = sums.at[0].add(-float(pad) * codebook[0])
        counts = counts.at[0].add(-float(pad))
        assign = assign[:p]
    return assign, sums, counts


def lloyd_step(w: jnp.ndarray, codebook: jnp.ndarray,
               use_pallas: bool | str = "auto") -> jnp.ndarray:
    _, sums, counts = assign_moments(w, codebook, use_pallas)
    new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), codebook)
    return jnp.sort(new)


def kmeans(w: jnp.ndarray, codebook0: jnp.ndarray, iters: int = 25,
           use_pallas: bool | str = "auto"):
    """Full Lloyd loop on the kernel; returns (codebook, assignments)."""
    cb = jnp.sort(codebook0)
    for _ in range(iters):
        cb = lloyd_step(w, cb, use_pallas)
    assign, _, _ = assign_moments(w, cb, use_pallas)
    return cb, assign


# ----------------------------------------------------------------------
# batched solver — the "kmeans_lloyd" entry of the kernel dispatch layer
# ----------------------------------------------------------------------
def assign_moments_batched(w: jnp.ndarray, codebooks: jnp.ndarray,
                           interpret: bool = True,
                           block_rows: int = ROWS):
    """Batched assignment + moments over a packed (I, P) item stack;
    pads each row internally (pad values clone each item's
    ``codebook[0]`` so padded elements land in cluster 0, then their
    contribution is subtracted from the moments). ``block_rows`` is the
    planner-chosen items-grid tile height (padding adapts to it)."""
    n_items, p = w.shape
    tile = int(block_rows) * LANES
    pad = (-p) % tile
    if pad:
        wp = jnp.concatenate(
            [w, jnp.broadcast_to(codebooks[:, :1], (n_items, pad))
             .astype(w.dtype)], axis=1)
    else:
        wp = w
    assign, sums, counts = kmeans_assign_moments_batched(
        wp, codebooks, interpret=interpret, block_rows=int(block_rows))
    if pad:
        sums = sums.at[:, 0].add(-float(pad) * codebooks[:, 0])
        counts = counts.at[:, 0].add(-float(pad))
        assign = assign[:, :p]
    return assign, sums, counts


def kmeans_batched(w: jnp.ndarray, codebooks0: jnp.ndarray,
                   kvalid: jnp.ndarray | None = None,
                   iters: int = 25, impl: str = "jnp",
                   block_rows: int = ROWS):
    """Per-item Lloyd loop over a packed (I, P) item stack with per-item
    (I, K) warm-start codebooks → (codebooks (I, K), assign (I, P)).

    ``kvalid`` (optional, (I,) i32) is the traced per-item count of
    *live* codebook entries — the mixed-K grouping operand. Codebooks
    arrive padded to the group-wide ``K_max`` (trailing entries are
    don't-care); entries at or beyond ``kvalid_i`` are pinned to +inf,
    so no weight ever assigns to them (distance +inf), their cluster
    moments stay empty, and the ascending sort keeps each item's live
    entries in the first ``kvalid_i`` slots — which is what lets the
    grouped engine slice per-task codebooks back out of the padded
    stack. With ``kvalid=None`` (or all-K_max) the masking is the
    identity and the solve is unchanged (bit-identical on ``"jnp"``).

    ``impl``: ``"jnp"`` maps the core compare-count solver over the
    items one at a time (bit-identical to the per-task scheme path). A
    vmapped solve would let a TPU tile each reduction by the launch's
    item count (a (3, P) stack gets (4, 128) tiles, a (10, P) stack
    (8, 128)), which changes the order of the cluster sums; mapped, every
    item runs the same compiled program, so a mesh shard's few items
    give the bits of the whole group on one device. ``"interpret"`` /
    ``"pallas"`` run the batched items-grid kernel — one pallas_call per
    Lloyd step for the whole group, per-item codebooks VMEM-resident.
    The kernel's moment accumulation order differs from the jnp masked
    reduce, so codebooks agree to float tolerance (not bitwise); see
    tests/test_kernel_dispatch.py for the enforced bounds.
    """
    if kvalid is not None:
        k_max = codebooks0.shape[-1]
        live = (jnp.arange(k_max)[None, :]
                < jnp.asarray(kvalid, jnp.int32)[:, None])
        codebooks0 = jnp.where(live, codebooks0.astype(jnp.float32),
                               jnp.inf)
    if impl == "jnp":
        # deferred import: kernels must stay importable without core
        # (core.grouping imports the dispatch layer at module load)
        from repro.core.schemes.quantize import kmeans_1d
        return jax.lax.map(lambda wc: kmeans_1d(*wc, iters),
                           (w, codebooks0))
    interpret = impl != "pallas"
    w = w.astype(jnp.float32)
    cb = jnp.sort(codebooks0.astype(jnp.float32), axis=-1)
    for _ in range(iters):
        _, sums, counts = assign_moments_batched(
            w, cb, interpret=interpret, block_rows=block_rows)
        cb = jnp.sort(jnp.where(counts > 0,
                                sums / jnp.maximum(counts, 1.0), cb),
                      axis=-1)
    assign, _, _ = assign_moments_batched(w, cb, interpret=interpret,
                                          block_rows=block_rows)
    return cb, assign
