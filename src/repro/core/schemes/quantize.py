"""Quantization C steps (paper §4.1).

Adaptive codebook quantization is the scalar k-means problem (eq. 2). Two
solvers are provided:

* ``AdaptiveQuantization`` — Lloyd iterations, warm-started across C steps.
  The nearest-centroid assignment counts codebook midpoints below each
  weight (bit-identical to ``searchsorted``, but a fused compare-reduce
  that stays fast under vmap for grouped C steps); cluster moments are
  masked reductions rather than scatter-adds. O(P·K) fused compute, O(P)
  memory — *no materialized* (P, K) distance matrix, which matters at
  P ~ 10⁹ and keeps the C step sharding-friendly (the only cross-shard
  traffic is the K-sized cluster-moment reductions). Δ(Θ) =
  codebook[assign] is a tree of selects up to K = ``SELECT_MAX_K``,
  not a gather: on a TPU the gather costs 11-13 ns an element, and
  was 94 % of a phi3-mini C step (``AdaptiveQuantization.decompress``).
* ``optimal_codebook_dp`` — globally optimal 1-D quantizer via dynamic
  programming on a B-bin histogram (exact on the binned distribution;
  replaces the O(K·P²) exact DP of Bruce/Wu, see DESIGN.md §8.3).

Fixed-form schemes: ``Binarize`` into {−1,1} or {−c,c} (optimal scale
c = mean|w|), ``Ternarize`` into {−c,0,c} with jointly optimal support and
scale (sort + cumsum argmax, per Carreira-Perpiñán & Idelbayev 2017 [4]).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.schemes.base import CompressionScheme


class QuantTheta(NamedTuple):
    codebook: jnp.ndarray  # (K,) float32
    assign: jnp.ndarray    # (P,) int32 — index into codebook


#: largest codebook ``AdaptiveQuantization.decompress`` reads by
#: selects; larger ones gather. 256 covers every 1- to 8-bit codebook.
SELECT_MAX_K = 256


def lookup_kind(k: int) -> str:
    """How ``AdaptiveQuantization.decompress`` reads a K-entry
    codebook: ``"select"`` or ``"gather"``."""
    return "select" if k <= SELECT_MAX_K else "gather"


def _assign_nearest(w: jnp.ndarray, codebook: jnp.ndarray) -> jnp.ndarray:
    """Nearest-centroid assignment for a *sorted* 1-D codebook.

    Counting midpoints below each w is bit-identical to
    ``searchsorted(midpoints, w, side='left')`` (ties included) but is a
    fused K-way compare-reduce: no serial binary-search chain, and it
    batches cleanly under vmap (grouped C steps) — searchsorted's gather
    loop degrades ~2× when the haystack is batched.
    """
    midpoints = (codebook[1:] + codebook[:-1]) * 0.5
    return jnp.sum((w[..., None] > midpoints).astype(jnp.int32), axis=-1)


def _cluster_moments(w, assign, k: int):
    """Per-cluster (Σw, count) via masked reductions.

    XLA fuses the broadcast-compare-select into the reduce — O(P) memory
    like segment_sum, but ~5× faster on CPU (scatter-adds serialize) and
    vmap-neutral for the grouped C step.
    """
    onehot = assign[..., None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]
    sums = jnp.sum(jnp.where(onehot, w[..., None, :], 0.0), axis=-1)
    counts = jnp.sum(onehot.astype(jnp.float32), axis=-1)
    return sums, counts


def _lloyd_update(w, codebook):
    """One Lloyd step: assign to nearest centroid, recompute means."""
    k = codebook.shape[0]
    assign = _assign_nearest(w, codebook)
    sums, counts = _cluster_moments(w, assign, k)
    new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), codebook)
    return jnp.sort(new)


def kmeans_1d(w: jnp.ndarray, codebook0: jnp.ndarray, iters: int = 25):
    """Scalar k-means with warm start; returns (codebook, assignments).

    Small static ``iters`` unrolls instead of lowering to ``lax.while``:
    XLA keeps cross-iteration fusion and (on CPU) intra-op threading,
    which a while body forfeits — measurably faster both per-task and
    under the grouped C step's vmap. Large ``iters`` falls back to
    ``fori_loop`` to keep program size (and compile time) bounded.
    """
    w = w.astype(jnp.float32)
    codebook = jnp.sort(codebook0)
    if iters <= 32:
        for _ in range(iters):
            codebook = _lloyd_update(w, codebook)
    else:
        codebook = jax.lax.fori_loop(
            0, iters, lambda _, c: _lloyd_update(w, c), codebook)
    return codebook, _assign_nearest(w, codebook)


def quantile_init(w: jnp.ndarray, k: int) -> jnp.ndarray:
    """Deterministic k-means init: K equally-spaced quantiles of w."""
    qs = (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
    return jnp.quantile(w.astype(jnp.float32), qs)


class AdaptiveQuantization(CompressionScheme):
    """Learned codebook of size K via scalar k-means (paper eq. 2)."""

    domain = "vector"
    # batched Lloyd solver in the kernel dispatch registry: on TPU the
    # grouped C step runs one items-grid Pallas launch per group instead
    # of vmapping kmeans_1d (see kernels/dispatch.py; the jnp backend is
    # bit-identical to the vmap path)
    solver = "kmeans_lloyd"
    solver_operands = ("kvalid",)

    def __init__(self, k: int = 2, iters: int = 25, use_dp_init: bool = False,
                 dp_bins: int = 2048):
        assert k >= 2
        self.k = int(k)
        self.iters = int(iters)
        self.use_dp_init = bool(use_dp_init)
        self.dp_bins = int(dp_bins)

    def group_key(self):
        return ("quant-kmeans", self.k, self.iters)

    def batch_key(self):
        # K shapes the codebook arrays, so it can't be a plain operand
        # like κ — instead codebooks pad to the group K_max
        # (pack_thetas_padded) and K rides as the traced per-item
        # *valid-entry count*: tasks differing only in K pack into one
        # group and one launch (mixed-K grouping). iters still shapes
        # the traced Lloyd loop and stays in the key.
        return ("quant-kmeans", self.iters)

    def batch_operands(self, n_items: int):
        return (jnp.full((n_items,), self.k, jnp.int32),)

    @classmethod
    def contract_examples(cls):
        # tiny iters: the lint HLO layer lowers this, it never runs it
        return (cls(k=2, iters=2),)

    def init_key(self):
        # the DP warm start only changes init(), not compress(): keep it
        # out of group_key (C-step groups merge across it) but in the
        # init grouping identity (Θ^DC differs)
        return (*self.group_key(), self.use_dp_init, self.dp_bins)

    def init(self, w, key=None):
        if self.use_dp_init:
            cb = optimal_codebook_dp(w, self.k, bins=self.dp_bins)
        else:
            cb = quantile_init(w, self.k)
        cb, assign = kmeans_1d(w, cb, self.iters)
        return QuantTheta(cb, assign)

    def compress(self, w, theta: QuantTheta, mu=None):
        cb, assign = kmeans_1d(w, theta.codebook, self.iters)
        return QuantTheta(cb, assign)

    def compress_batched(self, solve, w, theta: QuantTheta, operands,
                         mu=None):
        """One solver call warm-starts every item's codebook at once
        (w (I, P), theta.codebook (I, K_max) padded to the group max,
        operands = (per-item live-entry counts,)). Padded entries are
        pinned to +inf inside the solver, so each item's live codebook
        stays in the leading slots for the per-task slice-back."""
        (kvalid,) = operands
        cb, assign = solve(w, theta.codebook, kvalid, iters=self.iters)
        return QuantTheta(cb, assign)

    def decompress(self, theta: QuantTheta):
        """Δ(Θ) = codebook[assign], with the gather's bits.

        On a TPU the gather costs about 12 ns an element: vmapped over
        3 × 25.2 M weights it takes 949 ms on a v5e, against 1.9 ms for
        a tree of K - 1 selects on static codebook slices, one level per
        bit of ``assign``, which is one loop fusion (1.98 ms for a chain
        of ``assign == k`` selects; at K = 256 the tree takes 19 ms and
        the chain 130 ms). Under the grouped C step's vmap the static
        slices stay slices of the (I, K) codebook stack, never a gather.
        Selects only, no arithmetic on the codebook: mixed-K groups pad
        codebooks with +inf, and a one-hot product would make
        ``inf * 0`` a NaN. Codebooks beyond ``SELECT_MAX_K`` entries
        keep the gather (:func:`lookup_kind`).
        """
        cb, assign = theta.codebook, theta.assign
        k = cb.shape[-1]
        if lookup_kind(k) == "gather":
            return cb[assign]
        # level b picks between pairs of the previous level by bit b;
        # an unpaired last entry only holds indices whose bit b is 0
        vals = [cb[i] for i in range(k)]
        bit = 0
        while len(vals) > 1:
            high = (assign & (1 << bit)) != 0
            vals = [jnp.where(high, vals[j + 1], vals[j])
                    if j + 1 < len(vals) else vals[j]
                    for j in range(0, len(vals), 2)]
            bit += 1
        return jnp.broadcast_to(vals[0], assign.shape)

    def bits(self, theta: QuantTheta, float_bits: int = 32):
        p = theta.assign.size
        import math
        return p * math.ceil(math.log2(self.k)) + self.k * float_bits


class Binarize(CompressionScheme):
    """{−1,1} (``scaled=False``) or {−c,c} with optimal c = mean|w|."""

    domain = "vector"

    def __init__(self, scaled: bool = True):
        self.scaled = bool(scaled)

    def group_key(self):
        return ("quant-binarize", self.scaled)

    def init(self, w, key=None):
        return self.compress(w, None)

    def compress(self, w, theta, mu=None):
        w = w.astype(jnp.float32)
        sign = jnp.where(w >= 0, jnp.int8(1), jnp.int8(-1))
        scale = jnp.mean(jnp.abs(w)) if self.scaled else jnp.float32(1.0)
        return {"sign": sign, "scale": scale}

    def decompress(self, theta):
        return theta["sign"].astype(jnp.float32) * theta["scale"]

    def bits(self, theta, float_bits: int = 32):
        return theta["sign"].size + (float_bits if self.scaled else 0)


class Ternarize(CompressionScheme):
    """{−c,0,c} with jointly optimal support and scale.

    For support size s over the s largest |w|, the distortion reduction is
    (Σ_{top-s} |w|)² / s; we maximize it over s in one sort + cumsum pass.
    """

    domain = "vector"

    def group_key(self):
        return ("quant-ternarize",)

    def init(self, w, key=None):
        return self.compress(w, None)

    def compress(self, w, theta, mu=None):
        w = w.astype(jnp.float32)
        a = jnp.abs(w)
        a_sorted = jnp.sort(a)[::-1]
        csum = jnp.cumsum(a_sorted)
        s_range = jnp.arange(1, a.size + 1, dtype=jnp.float32)
        gain = csum**2 / s_range
        s_star = jnp.argmax(gain)
        c = csum[s_star] / (s_star + 1.0)
        thresh = a_sorted[s_star]  # keep |w| >= a_sorted[s*] (s*+1 items)
        sign = jnp.where(
            a >= thresh, jnp.where(w >= 0, jnp.int8(1), jnp.int8(-1)),
            jnp.int8(0))
        return {"sign": sign, "scale": c}

    def decompress(self, theta):
        return theta["sign"].astype(jnp.float32) * theta["scale"]

    def bits(self, theta, float_bits: int = 32):
        return theta["sign"].size * 1.585 + float_bits


# ----------------------------------------------------------------------
# Globally optimal 1-D quantizer on a histogram (DP).
# ----------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k", "bins"))
def optimal_codebook_dp(w: jnp.ndarray, k: int, bins: int = 2048):
    """Exact K-level scalar quantizer on a B-bin histogram of w.

    Cost of covering bins [i..j] with one level is the weighted SSE around
    the weighted mean; DP over levels with full (B, B) interval-cost matrix.
    O(K·B²) time, O(B²) memory — independent of P.
    """
    w = w.astype(jnp.float32).ravel()
    lo, hi = jnp.min(w), jnp.max(w)
    width = jnp.maximum(hi - lo, 1e-12)
    centers = lo + (jnp.arange(bins, dtype=jnp.float32) + 0.5) / bins * width
    idx = jnp.clip(((w - lo) / width * bins).astype(jnp.int32), 0, bins - 1)
    h0 = jax.ops.segment_sum(jnp.ones_like(w), idx, num_segments=bins)
    h1 = h0 * centers
    h2 = h0 * centers**2

    # prefix sums with leading zero: S[j] - S[i] = bins i..j-1
    z = jnp.zeros((1,), jnp.float32)
    s0, s1, s2 = (jnp.concatenate([z, jnp.cumsum(h)]) for h in (h0, h1, h2))

    def interval_cost(i, j):  # bins [i, j) — i, j broadcastable int arrays
        n = s0[j] - s0[i]
        m1 = s1[j] - s1[i]
        m2 = s2[j] - s2[i]
        return jnp.where(n > 0, m2 - m1**2 / jnp.maximum(n, 1.0), 0.0)

    ii = jnp.arange(bins + 1)
    cost = interval_cost(ii[:, None], ii[None, :])          # (B+1, B+1)
    cost = jnp.where(ii[:, None] <= ii[None, :], cost, jnp.inf)

    # E[j] = best cost of covering bins [0, j) with the current # of levels
    e = cost[0]                                              # 1 level
    big = jnp.float32(jnp.inf)

    def level(e_prev, _):
        # E_new[j] = min_i E_prev[i] + cost[i, j]
        tot = e_prev[:, None] + cost                          # (B+1, B+1)
        e_new = jnp.min(tot, axis=0)
        arg = jnp.argmin(tot, axis=0)
        return e_new, arg

    e_final, args = jax.lax.scan(level, e, None, length=k - 1)
    del big

    # Backtrack split points: start at j = B, walk levels k-1 .. 1.
    def back(j, level_args):
        i = level_args[j]
        return i, j

    js = [jnp.int32(bins)]
    j = jnp.int32(bins)
    for lvl in range(k - 2, -1, -1):
        j = args[lvl][j]
        js.append(j)
    js = jnp.stack(js[::-1])  # (k,) right edges ascending, js[-1] = B
    lefts = jnp.concatenate([jnp.zeros((1,), jnp.int32), js[:-1]])

    n = s0[js] - s0[lefts]
    m1 = s1[js] - s1[lefts]
    cb = jnp.where(n > 0, m1 / jnp.maximum(n, 1.0), centers[jnp.clip(lefts, 0, bins - 1)])
    return jnp.sort(cb)
