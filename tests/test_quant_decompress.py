"""k-means decompress Δ(Θ) = codebook[assign] without a gather.

Up to ``SELECT_MAX_K`` entries ``AdaptiveQuantization.decompress`` is a
tree of selects on static codebook slices; it must give the bits of the
gather for every valid assignment, unbatched and under the grouped C
step's vmap, with mixed-K groups' +inf padding past each item's live
entries. Beyond ``SELECT_MAX_K`` it keeps the gather.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AsStacked, AsVector, CompressionTask, LCAlgorithm
from repro.core.schemes import AdaptiveQuantization, ConstraintL0Pruning
from repro.core.schemes.quantize import (
    SELECT_MAX_K, QuantTheta, lookup_kind)

P = 1000


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _codebook(key, k):
    cb = jax.random.normal(key, (k,), jnp.float32)
    # zeros of both signs: equal as floats, told apart only by their bits
    return cb.at[0].set(-0.0).at[1].set(0.0)


def _assign(key, kvalid):
    """Every live entry at least once (0 and kvalid - 1 included), the
    rest drawn uniformly over the live entries."""
    head = jnp.arange(kvalid, dtype=jnp.int32)
    tail = jax.random.randint(key, (P - kvalid,), 0, kvalid, jnp.int32)
    return jax.random.permutation(key, jnp.concatenate([head, tail]))


@pytest.mark.parametrize("k", [2, 3, 5, 16, 17, 256, 257])
def test_decompress_matches_gather_bits(k):
    key = jax.random.PRNGKey(k)
    scheme = AdaptiveQuantization(k=k, iters=1)

    # unbatched
    cb = _codebook(key, k)
    assign = _assign(jax.random.fold_in(key, 1), k)
    got = jax.jit(scheme.decompress)(QuantTheta(cb, assign))
    np.testing.assert_array_equal(_bits(got), _bits(cb[assign]))

    # vmapped (I, K) codebooks, as a mixed-K group packs them: entries
    # past each item's kvalid are +inf and never assigned
    kvalid = sorted({k, max(2, k // 2), 2})
    cbs, assigns = [], []
    for i, kv in enumerate(kvalid):
        c = _codebook(jax.random.fold_in(key, 10 + i), k)
        cbs.append(jnp.where(jnp.arange(k) < kv, c, jnp.inf))
        assigns.append(_assign(jax.random.fold_in(key, 20 + i), kv))
    theta = QuantTheta(jnp.stack(cbs), jnp.stack(assigns))
    got = jax.jit(jax.vmap(scheme.decompress))(theta)
    want = jax.vmap(lambda c, a: c[a])(theta.codebook, theta.assign)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert np.isfinite(np.asarray(got)).all()

    # the lowering follows the K rule, under vmap too
    lowered = jax.jit(jax.vmap(scheme.decompress)).lower(theta).as_text()
    assert ("stablehlo.gather" in lowered) == (lookup_kind(k) == "gather")


def test_vmapped_decompress_lowers_without_gather():
    theta = QuantTheta(jax.ShapeDtypeStruct((3, 16), jnp.float32),
                       jax.ShapeDtypeStruct((3, P), jnp.int32))
    text = jax.jit(jax.vmap(AdaptiveQuantization(k=16).decompress)) \
        .lower(theta).as_text()
    assert "stablehlo.gather" not in text


def test_lookup_kind_rule():
    assert SELECT_MAX_K == 256
    assert [lookup_kind(k) for k in (2, 16, 256, 257, 1024)] == \
        ["select"] * 3 + ["gather"] * 2


def test_group_summary_reports_decompress():
    """Two multi-task k-means groups at K = 16, as the attention and MLP
    projections form them, a K = 300 task and a pruning task."""
    key = jax.random.PRNGKey(0)
    shapes = {"wq": (96, 32), "wk": (96, 32), "w_up": (64, 64),
              "w_down": (64, 64), "big": (2, 512), "p": (128,)}
    params = {n: jax.random.normal(jax.random.fold_in(key, i), s)
              for i, (n, s) in enumerate(shapes.items())}
    tasks = [CompressionTask(n, rf"^{n}$", AsVector(),
                             AdaptiveQuantization(k=16, iters=2))
             for n in ("wq", "wk", "w_up", "w_down")]
    tasks += [CompressionTask("big", r"^big$", AsStacked("vector"),
                              AdaptiveQuantization(k=300, iters=2)),
              CompressionTask("p", r"^p$", AsVector(),
                              ConstraintL0Pruning(kappa=8))]
    summary = LCAlgorithm(tasks, [1e-2]).group_summary(params)
    got = {tuple(g["tasks"]): (g["grouped"], g["decompress"])
           for g in summary}
    assert got == {("wq", "wk"): (True, "select"),
                   ("w_up", "w_down"): (True, "select"),
                   ("big",): (False, "gather"), ("p",): (False, None)}
