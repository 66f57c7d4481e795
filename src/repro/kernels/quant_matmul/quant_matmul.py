"""Pallas TPU kernels: fused codebook-dequant GEMM for compressed serving.

After LC adaptive quantization, weights are stored as uint8 codebook
indices (+ a K≤16-entry f32 codebook). Serving decode is memory-bound —
streaming uint8 indices instead of bf16 weights cuts the dominant HBM
term ~2× and **4-bit packing** (two indices per byte, unpacked with
nibble bitwise ops *inside* the kernel) cuts it ~4×; full-width weights
never touch HBM in either form.

TPU adaptation of the GPU LUT-gather: Mosaic has no fast VMEM gather by
vector index, so dequant is a **compare–select accumulation over the K
codebook entries** (K ≤ 16 ⇒ 16 VPU select-FMAs per tile element,
amortized over the MXU matmul): W_tile = Σ_c cb[c]·(idx_tile == c).

Grid (M/bm, N/bn, K/bk), k innermost; the f32 accumulator lives in the
output ref block, zero-initialized at k==0 (grid-sequential revisiting).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, idx_ref, cb_ref, y_ref, *, n_codes: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]                                   # (bm, bk)
    idx = idx_ref[...]                               # (bk, bn) uint8
    cb = cb_ref[...]                                 # (1, C)
    # compare–select dequant: W = Σ_c cb[c] · (idx == c)
    w = jnp.zeros(idx.shape, jnp.float32)
    for c in range(n_codes):
        w += jnp.where(idx == c, cb[0, c], 0.0)
    y_ref[...] += jnp.dot(x.astype(jnp.float32), w,
                          preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul(x: jnp.ndarray, idx: jnp.ndarray, codebook: jnp.ndarray,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 interpret: bool = True) -> jnp.ndarray:
    """y = x @ codebook[idx]. Shapes must tile exactly (ops.py pads)."""
    m, k = x.shape
    k2, n = idx.shape
    assert k == k2
    c = codebook.shape[0]
    assert c <= 16, "compare-select dequant is for K ≤ 16 codebooks"
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0

    return pl.pallas_call(
        partial(_kernel, n_codes=c),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, c), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, idx, codebook.reshape(1, c).astype(jnp.float32))


# ----------------------------------------------------------------------
# 4-bit packed variant (two indices per byte)
# ----------------------------------------------------------------------
def _packed_kernel(xe_ref, xo_ref, packed_ref, cb_ref, y_ref, *,
                   n_codes: int):
    """Packed byte b at (r, j) holds indices of W rows 2r (low nibble)
    and 2r+1 (high nibble), column j. The caller pre-splits x into its
    even and odd K-columns, so unpacking never reshapes/interleaves in
    VMEM: y += x_even @ W_low + x_odd @ W_high.
    """
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    xe = xe_ref[...].astype(jnp.float32)             # (bm, bk2)
    xo = xo_ref[...].astype(jnp.float32)             # (bm, bk2)
    # unpack in int32: Mosaic cannot relayout the i1 masks of 8-bit
    # compares, and the 32-bit lanes match the f32 select below
    packed = packed_ref[...].astype(jnp.int32)        # (bk2, bn)
    cb = cb_ref[...]                                  # (1, C)
    lo = packed & 0x0F
    hi = packed >> 4
    w_lo = jnp.zeros(packed.shape, jnp.float32)
    w_hi = jnp.zeros(packed.shape, jnp.float32)
    for c in range(n_codes):
        w_lo += jnp.where(lo == c, cb[0, c], 0.0)
        w_hi += jnp.where(hi == c, cb[0, c], 0.0)
    y_ref[...] += (jnp.dot(xe, w_lo, preferred_element_type=jnp.float32)
                   + jnp.dot(xo, w_hi,
                             preferred_element_type=jnp.float32))


@partial(jax.jit, static_argnames=("bm", "bn", "bk2", "interpret"))
def quant_matmul_packed(x_even: jnp.ndarray, x_odd: jnp.ndarray,
                        packed: jnp.ndarray, codebook: jnp.ndarray,
                        bm: int = 128, bn: int = 128, bk2: int = 256,
                        interpret: bool = True) -> jnp.ndarray:
    """y = x @ codebook[unpack4(packed)] with x pre-split into even/odd
    K-columns (x_even = x[:, 0::2], x_odd = x[:, 1::2]). Shapes must
    tile exactly (ops.py pads)."""
    m, k2 = x_even.shape
    assert x_odd.shape == (m, k2)
    k2b, n = packed.shape
    assert k2 == k2b
    c = codebook.shape[0]
    assert c <= 16, "4-bit packing needs a K ≤ 16 codebook"
    bm, bn, bk2 = min(bm, m), min(bn, n), min(bk2, k2)
    assert m % bm == 0 and n % bn == 0 and k2 % bk2 == 0

    return pl.pallas_call(
        partial(_packed_kernel, n_codes=c),
        grid=(m // bm, n // bn, k2 // bk2),
        in_specs=[
            pl.BlockSpec((bm, bk2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, c), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x_even, x_odd, packed, codebook.reshape(1, c).astype(jnp.float32))
