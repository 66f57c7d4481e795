"""Pallas TPU kernels for ℓ0-constraint pruning at scale (paper §4.2).

The C step keeps the top-κ weights by magnitude. A global sort of 10⁹
weights is the GPU-ish answer; the TPU-native adaptation is **threshold
bisection**: ~25 iterations of a streaming `count(|w| > t)` kernel (one
compare per element, grid-sequential scalar accumulation — the same
pattern as the k-means moments), then one `mask-apply` pass. 26 cheap
HBM sweeps beat a distributed sort, and every pass is embarrassingly
shardable (the count psums across shards).

Batched variants (:func:`count_above_batched`,
:func:`mask_apply_batched`) add an **items grid dimension** for the
grouped C step: grid ``(items, n_tiles)``, a per-item threshold block in
VMEM, a per-item count accumulator re-initialized when the (fast) tile
coordinate wraps. Per-item scalars are held as ``(I, 1, 1)`` arrays so
that their ``(1, 1, 1)`` blocks span the full last two dimensions (the
TPU's (8, 128) tiling rule). The threshold — and therefore κ, which the bisection
driver in ops.py compares the counts against — is a *traced per-item
operand*, which is what lets tasks that differ only in κ share one
kernel launch (mixed-κ grouping).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8
LANES = 128


def _count_kernel(w_ref, t_ref, out_ref):
    step = pl.program_id(0)
    w = w_ref[...]
    t = t_ref[0, 0]
    c = jnp.sum((jnp.abs(w) > t).astype(jnp.float32))[None, None]

    @pl.when(step == 0)
    def _init():
        out_ref[...] = c

    @pl.when(step != 0)
    def _accum():
        out_ref[...] += c


def _mask_kernel(w_ref, t_ref, out_ref):
    w = w_ref[...]
    t = t_ref[0, 0]
    out_ref[...] = jnp.where(jnp.abs(w) > t, w, 0.0)


@partial(jax.jit, static_argnames=("interpret",))
def count_above(w: jnp.ndarray, t: jnp.ndarray, interpret: bool = True):
    """w: (P,) padded to ROWS·LANES multiples; t: scalar → count f32."""
    p = w.shape[0]
    tile = ROWS * LANES
    assert p % tile == 0
    n_tiles = p // tile
    w2 = w.astype(jnp.float32).reshape(n_tiles * ROWS, LANES)
    out = pl.pallas_call(
        _count_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=interpret,
    )(w2, t.reshape(1, 1).astype(jnp.float32))
    return out[0, 0]


@partial(jax.jit, static_argnames=("interpret",))
def mask_apply(w: jnp.ndarray, t: jnp.ndarray, interpret: bool = True):
    p = w.shape[0]
    tile = ROWS * LANES
    assert p % tile == 0
    n_tiles = p // tile
    w2 = w.astype(jnp.float32).reshape(n_tiles * ROWS, LANES)
    out = pl.pallas_call(
        _mask_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * ROWS, LANES),
                                       jnp.float32),
        interpret=interpret,
    )(w2, t.reshape(1, 1).astype(jnp.float32))
    return out.reshape(p)


# ----------------------------------------------------------------------
# batched (items-grid) variants — one pallas_call per packed group.
# ``strict`` picks the comparison (|w| > t vs |w| ≥ t): the bisection
# driver in ops.py bisects on the ≥ form (feasibility: count(|w| ≥ t)
# ≥ κ) so its lo threshold never drops a whole tied class; the driver
# then resolves boundary ties down to exactly κ in index order.
# ----------------------------------------------------------------------
def _count_batched_kernel(w_ref, t_ref, out_ref, *, strict: bool):
    tile = pl.program_id(1)                      # fast axis: tiles
    w = w_ref[0]                                 # (ROWS, LANES)
    t = t_ref[0, 0, 0]                           # this item's threshold
    keep = jnp.abs(w) > t if strict else jnp.abs(w) >= t
    c = jnp.sum(keep.astype(jnp.float32))[None, None]

    @pl.when(tile == 0)
    def _init():
        out_ref[0] = c

    @pl.when(tile != 0)
    def _accum():
        out_ref[0] += c


def _mask_batched_kernel(w_ref, t_ref, out_ref, *, strict: bool):
    w = w_ref[0]
    t = t_ref[0, 0, 0]
    keep = jnp.abs(w) > t if strict else jnp.abs(w) >= t
    out_ref[0] = jnp.where(keep, w, 0.0)


def _tiled(w: jnp.ndarray, rows: int = ROWS):
    n_items, p = w.shape
    assert rows >= ROWS and rows % ROWS == 0, rows
    tile = rows * LANES
    assert p % tile == 0, f"pad to a multiple of {tile} in ops.py"
    n_tiles = p // tile
    return (w.astype(jnp.float32).reshape(n_items, n_tiles * rows, LANES),
            n_tiles)


@partial(jax.jit, static_argnames=("interpret", "strict", "block_rows"))
def count_above_batched(w: jnp.ndarray, t: jnp.ndarray,
                        interpret: bool = True, strict: bool = True,
                        block_rows: int = ROWS):
    """w: (I, P) padded; t: (I,) per-item thresholds → counts (I,) f32.
    ``block_rows``: planner-tunable sublane tile height (multiple of 8)."""
    n_items, p = w.shape
    rows = int(block_rows)
    w3, n_tiles = _tiled(w, rows)
    out = pl.pallas_call(
        partial(_count_batched_kernel, strict=strict),
        grid=(n_items, n_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_items, 1, 1), jnp.float32),
        interpret=interpret,
    )(w3, t.reshape(n_items, 1, 1).astype(jnp.float32))
    return out[:, 0, 0]


@partial(jax.jit, static_argnames=("interpret", "strict", "block_rows"))
def mask_apply_batched(w: jnp.ndarray, t: jnp.ndarray,
                       interpret: bool = True, strict: bool = True,
                       block_rows: int = ROWS):
    """w: (I, P) padded; t: (I,) → w·1[|w| > t_i] per item, (I, P)
    (``strict=False``: |w| ≥ t_i)."""
    n_items, p = w.shape
    rows = int(block_rows)
    w3, n_tiles = _tiled(w, rows)
    out = pl.pallas_call(
        partial(_mask_batched_kernel, strict=strict),
        grid=(n_items, n_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_items, n_tiles * rows, LANES), jnp.float32),
        interpret=interpret,
    )(w3, t.reshape(n_items, 1, 1).astype(jnp.float32))
    return out.reshape(n_items, p)
