"""Pallas TPU kernel: fused k-means assignment + cluster moments.

The adaptive-quantization C step (paper §4.1) assigns every weight to its
nearest codebook entry and accumulates per-cluster Σw / counts. On GPU
this is a gather + atomicAdd pattern; the TPU-native shape is:

* weights stream HBM→VMEM in (ROWS, 128) tiles (lane dim = 128);
* the codebook (K ≤ 256 f32) stays VMEM-resident across the whole grid
  (BlockSpec index_map pins block (0,) for every grid step);
* distance/argmin run on the VPU via broadcast-subtract-square over the
  K axis (K is small — the (r, 128, K) intermediate fits VMEM);
* cluster moments use **grid-sequential accumulation** into the output
  ref — TPU Pallas grids execute sequentially per core, which replaces
  CUDA atomics (`@pl.when(step == 0)` zero-init, then `+=`).

Two entry points share the kernel body:

* :func:`kmeans_assign_moments` — one weight vector, grid ``(n_tiles,)``.
* :func:`kmeans_assign_moments_batched` — a packed *group* of items
  (the grouped C step's stacked leading axis), grid
  ``(items, n_tiles)``. Each item brings its own VMEM-resident codebook
  and its own moment accumulators, held as ``(I, 1, K)`` arrays so that
  a per-item block ``(1, 1, K)`` spans the full last two dimensions (the
  TPU's (8, 128) tiling rule); the tile coordinate is the fast axis, so the
  per-item accumulation runs grid-sequentially exactly like the
  unbatched kernel, and one ``pallas_call`` solves the whole group
  instead of vmapping the jnp solver.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8           # sublane tile rows
LANES = 128        # TPU lane width


def _kernel(w_ref, cb_ref, assign_ref, sums_ref, counts_ref, *, k: int):
    step = pl.program_id(0)
    w = w_ref[...]                                    # (ROWS, LANES) f32
    cb = cb_ref[...]                                  # (1, K) f32
    d = (w[:, :, None] - cb[0][None, None, :]) ** 2   # (ROWS, LANES, K)
    assign = jnp.argmin(d, axis=-1).astype(jnp.int32)
    assign_ref[...] = assign
    onehot = (assign[:, :, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2))
    onehot = onehot.astype(jnp.float32)
    part_sums = jnp.sum(w[:, :, None] * onehot, axis=(0, 1))[None, :]
    part_counts = jnp.sum(onehot, axis=(0, 1))[None, :]

    @pl.when(step == 0)
    def _init():
        sums_ref[...] = part_sums
        counts_ref[...] = part_counts

    @pl.when(step != 0)
    def _accum():
        sums_ref[...] += part_sums
        counts_ref[...] += part_counts


@partial(jax.jit, static_argnames=("interpret",))
def kmeans_assign_moments(w: jnp.ndarray, codebook: jnp.ndarray,
                          interpret: bool = True):
    """w: (P,) f32 (P % (ROWS·LANES) == 0 after ops.py padding);
    codebook: (K,) f32 → (assign (P,) i32, sums (K,), counts (K,))."""
    p = w.shape[0]
    k = codebook.shape[0]
    tile = ROWS * LANES
    assert p % tile == 0, f"pad to a multiple of {tile} in ops.py"
    n_tiles = p // tile
    w2 = w.astype(jnp.float32).reshape(n_tiles * ROWS, LANES)
    cb2 = codebook.astype(jnp.float32).reshape(1, k)

    assign2, sums2, counts2 = pl.pallas_call(
        partial(_kernel, k=k),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),   # pinned in VMEM
        ],
        out_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),   # sequential accum
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * ROWS, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(w2, cb2)
    return assign2.reshape(p), sums2[0], counts2[0]


def _batched_kernel(w_ref, cb_ref, assign_ref, sums_ref, counts_ref,
                    *, k: int):
    tile = pl.program_id(1)                           # fast axis: tiles
    w = w_ref[0]                                      # (ROWS, LANES) f32
    cb = cb_ref[0]                                    # (1, K) f32
    d = (w[:, :, None] - cb[0][None, None, :]) ** 2   # (ROWS, LANES, K)
    assign = jnp.argmin(d, axis=-1).astype(jnp.int32)
    assign_ref[0] = assign
    onehot = (assign[:, :, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2))
    onehot = onehot.astype(jnp.float32)
    part_sums = jnp.sum(w[:, :, None] * onehot, axis=(0, 1))[None, :]
    part_counts = jnp.sum(onehot, axis=(0, 1))[None, :]

    # the item's accumulator block is revisited once per tile; the grid
    # is row-major (tile fastest), so `tile == 0` re-inits per item
    @pl.when(tile == 0)
    def _init():
        sums_ref[0] = part_sums
        counts_ref[0] = part_counts

    @pl.when(tile != 0)
    def _accum():
        sums_ref[0] += part_sums
        counts_ref[0] += part_counts


@partial(jax.jit, static_argnames=("interpret", "block_rows"))
def kmeans_assign_moments_batched(w: jnp.ndarray, codebooks: jnp.ndarray,
                                  interpret: bool = True,
                                  block_rows: int = ROWS):
    """w: (I, P) f32 (P % (block_rows·LANES) == 0 after ops.py padding);
    codebooks: (I, K) f32 → (assign (I, P) i32, sums (I, K),
    counts (I, K)) — one pallas_call for the whole packed item group.

    ``block_rows`` is the planner-tunable sublane tile height (default
    the f32 minimum, 8; must be a multiple of 8). Larger tiles amortize
    grid overhead at the cost of VMEM per step — the group planner
    (``analysis/cost.choose_block_rows``) picks it per group.
    """
    n_items, p = w.shape
    k = codebooks.shape[-1]
    rows = int(block_rows)
    assert rows >= ROWS and rows % ROWS == 0, rows
    tile = rows * LANES
    assert p % tile == 0, f"pad to a multiple of {tile} in ops.py"
    n_tiles = p // tile
    w3 = w.astype(jnp.float32).reshape(n_items, n_tiles * rows, LANES)
    cb3 = codebooks.astype(jnp.float32).reshape(n_items, 1, k)

    assign3, sums3, counts3 = pl.pallas_call(
        partial(_batched_kernel, k=k),
        grid=(n_items, n_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, k), lambda i, j: (i, 0, 0)),  # per item
        ],
        out_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, k), lambda i, j: (i, 0, 0)),  # accum
            pl.BlockSpec((1, 1, k), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_items, n_tiles * rows, LANES),
                                 jnp.int32),
            jax.ShapeDtypeStruct((n_items, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((n_items, 1, k), jnp.float32),
        ],
        interpret=interpret,
    )(w3, cb3)
    return (assign3.reshape(n_items, p), sums3.reshape(n_items, k),
            counts3.reshape(n_items, k))
