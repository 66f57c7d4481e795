"""Plain float32 building blocks shared by the model references.

Nothing here imports the program under test. Every matrix product goes
through :class:`MatMul`, which casts both operands to one dtype and
accumulates in float32: ``float32`` (under
``jax.default_matmul_precision("highest")``) for the reference, a lower
dtype for the control that shows the comparison can fail.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HERE = Path(__file__).resolve().parent


class MatMul:
    """``mm(x, w)`` and ``mm.einsum(spec, a, b)`` with operands cast to
    ``dtype`` and float32 accumulation."""

    def __init__(self, dtype=F32):
        self.dtype = jnp.dtype(dtype)

    def _cast(self, a):
        """``(operand, scale)``: float32 as it is; a lower dtype scaled per
        tensor so that the largest magnitude meets the dtype's largest
        finite value, as low-precision training scales it."""
        a = a.astype(F32)
        if self.dtype == F32:
            return a, None
        if jnp.issubdtype(self.dtype, jnp.floating):
            hi = float(jnp.finfo(self.dtype).max)
            s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / hi
            return (a / s).astype(self.dtype), s
        hi = float(jnp.iinfo(self.dtype).max)
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / hi
        return jnp.round(a / s).astype(self.dtype), s

    def einsum(self, spec, a, b):
        (qa, sa), (qb, sb) = self._cast(a), self._cast(b)
        if sa is None:
            return jnp.einsum(spec, qa, qb, preferred_element_type=F32)
        acc = jnp.int32 if jnp.issubdtype(self.dtype, jnp.integer) else F32
        out = jnp.einsum(spec, qa, qb, preferred_element_type=acc)
        return out.astype(F32) * (sa * sb)

    def __call__(self, x, w):
        return self.einsum("...i,ij->...j", x, w)


def rms_norm(x, scale, eps):
    """RMSNorm with a zero-initialised gain: ``x / rms(x) * (1 + scale)``."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence. x (S, C), w (K, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x], 0)
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(k)) + b


def row_nll(logits, labels):
    """Summed next-token negative log-likelihood of one row."""
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - gold)


def switch_balance(probs, chosen, n_experts: int):
    """The Switch Transformer's load-balance term of one layer's routing,
    ``E * sum_e f_e * P_e`` (Fedus et al., arXiv:2101.03961): over the T
    tokens routed, ``f_e`` is the mean number of times a token chose
    expert e and ``P_e`` the mean router probability of e. ``probs`` (T,
    E), ``chosen`` (T, k) expert indices. The choice carries no gradient,
    the probabilities do."""
    hits = chosen[:, :, None] == jnp.arange(n_experts)[None, None, :]
    f = jnp.mean(jnp.sum(hits.astype(F32), axis=1), axis=0)
    p = jnp.mean(probs.astype(F32), axis=0)
    return n_experts * jnp.sum(f * p)


# ----------------------------------------------------------------------
# compression: plain k-means (Lloyd) and top-κ by sort
# ----------------------------------------------------------------------
def round_trip(a, dtype):
    """``a`` stored in ``dtype`` (scaled per tensor, as :class:`MatMul`
    scales an operand) and read back as float32."""
    q, scale = MatMul(dtype)._cast(a)
    return q if scale is None else q.astype(F32) * scale


def kmeans(w, k: int, iters: int, block: int = 1 << 20, dtype=None):
    """Scalar k-means (Lloyd) from the k quantiles at (i + 0.5)/k:
    ``iters`` steps of nearest-centroid assignment (ties to the lower
    centroid) and cluster means (an empty cluster keeps its centroid).
    Returns the quantised vector. The (P, k) distances are taken in
    blocks of ``block`` weights, so that tens of millions fit. With
    ``dtype`` the weights and every codebook are held in that precision
    (the control)."""
    w = w.astype(F32).ravel()
    low = (lambda a: a) if dtype is None else (lambda a: round_trip(a, dtype))
    w = low(w)
    n = w.size
    pad = (-n) % block
    wp = jnp.concatenate([w, jnp.full((pad,), jnp.nan, F32)]).reshape(-1, block)
    cb = low(jnp.sort(jnp.quantile(w, (jnp.arange(k, dtype=F32) + 0.5) / k)))

    def assign_block(cb, x):
        return jnp.argmin(jnp.abs(x[:, None] - cb[None, :]), axis=1)

    def moments(cb):
        def body(acc, x):
            a = assign_block(cb, x)
            hit = (a[:, None] == jnp.arange(k)[None, :]) \
                & ~jnp.isnan(x)[:, None]
            s = jnp.sum(jnp.where(hit, x[:, None], 0.0), axis=0)
            c = jnp.sum(hit.astype(F32), axis=0)
            return (acc[0] + s, acc[1] + c), None
        (s, c), _ = jax.lax.scan(body, (jnp.zeros(k, F32), jnp.zeros(k, F32)),
                                 wp)
        return s, c

    def lloyd(cb, _):
        s, c = moments(cb)
        return low(jnp.sort(jnp.where(c > 0, s / jnp.maximum(c, 1.0), cb))), None

    cb, _ = jax.lax.scan(lloyd, cb, None, length=iters)
    q = jax.lax.map(lambda x: cb[assign_block(cb, x)], wp)
    return q.reshape(-1)[:n]


def topk_keep(w, kappa: int):
    """Keep the ``kappa`` largest magnitudes of ``w`` (by a full sort),
    zero the rest."""
    flat = w.astype(F32).ravel()
    thresh = jnp.sort(jnp.abs(flat))[flat.size - kappa]
    return jnp.where(jnp.abs(flat) >= thresh, flat, 0.0).reshape(w.shape)


# ----------------------------------------------------------------------
# AdamW with global-norm clipping, as published (Loshchilov & Hutter)
# ----------------------------------------------------------------------
def clip_global(grads, max_norm):
    leaves = jax.tree_util.tree_leaves(grads)
    n = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    s = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * s, grads)


def adamw(params, grads, m, v, t, lr, b1, b2, eps, wd):
    """One AdamW step (t counts from 1). Returns params, m, v."""
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                               grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v


# ----------------------------------------------------------------------
def flatten(tree, prefix="") -> dict:
    """Nested dict → {"a/b/c": leaf} in sorted key order."""
    out = {}
    for k in sorted(tree):
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(flatten(tree[k], p))
        else:
            out[p] = tree[k]
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for p, v in flat.items():
        node = out
        keys = p.split("/")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = v
    return out


def dense_init(key, shape):
    """Normal weights with std 1/sqrt(fan_in), fan_in the first axis."""
    return jax.random.normal(key, shape, F32) / np.sqrt(max(shape[0], 1))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_reference(config_name: str):
    """The plain reference module kept beside a configuration's file
    (``configs/<config_name>.ref.py``; a path that is absolute is taken
    as it is)."""
    name = Path(config_name).name
    return load_module(HERE / "configs" / f"{config_name}.ref.py",
                       "ref_" + name.replace("-", "_").replace(".", "_"))
