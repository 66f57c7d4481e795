"""Plain float32 reference of the Phi-3-mini decoder (arXiv:2404.14219,
hf microsoft/Phi-3-mini-4k-instruct).

Pre-norm blocks: RMSNorm, multi-head attention with rotary position
embeddings (rotate-half form, base 10000) and a causal softmax over the
keys at most ``sliding_window`` positions back (2047, as the hub's
flash-attention path applies it), RMSNorm, SwiGLU MLP; final RMSNorm and
an untied head. One row at a time; the attention softmax is exact over
all keys it sees, taken in blocks of queries.

Departures from the published model, which the program under test makes
too and which this reference therefore follows:
  * token embeddings are scaled by sqrt(d_model) (a reparametrisation of
    the embedding table: the published model does not scale);
  * the RMSNorm gain is stored as (1 + scale), initialised at 0;
  * the query, key and value projections are separate matrices and the
    MLP's gate and up projections too (published: fused, same maths).
Weights are drawn from the seed exactly as the program draws them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from refs import F32, dense_init, rms_norm, silu

Q_BLOCK = 512


def layers(c):
    out = []
    for r in range(c["pattern_reps"]):
        for pi, _spec in enumerate(c["pattern"]):
            out.append((r if c["pattern_reps"] > 1 else None, pi, "attn"))
    return out


def _init_block(key, c):
    d, hd = c["d_model"], c["head_dim"]
    q, kv, ff = c["n_heads"] * hd, c["n_kv_heads"] * hd, c["d_ff"]
    k1, k2 = jax.random.split(key)
    ka = jax.random.split(k1, 4)
    kf = jax.random.split(k2, 3)
    return {
        "mixer_norm": jnp.zeros((d,), F32),
        "mixer": {"wq": dense_init(ka[0], (d, q)),
                  "wk": dense_init(ka[1], (d, kv)),
                  "wv": dense_init(ka[2], (d, kv)),
                  "wo": dense_init(ka[3], (q, d))},
        "ffn_norm": jnp.zeros((d,), F32),
        "ffn": {"w_gate": dense_init(kf[0], (d, ff)),
                "w_up": dense_init(kf[1], (d, ff)),
                "w_down": dense_init(kf[2], (ff, d))},
    }


def init_params(key, c):
    d, v = c["d_model"], c["vocab_size"]
    ke, _kh, ks = jax.random.split(key, 3)
    stage = {}
    for pi, _spec in enumerate(c["pattern"]):
        kk = jax.random.fold_in(ks, pi)
        reps = c["pattern_reps"]
        if reps > 1:
            keys = jax.random.split(kk, reps)
            blocks = [_init_block(keys[r], c) for r in range(reps)]
            stage[f"pos{pi}"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks)
        else:
            stage[f"pos{pi}"] = _init_block(kk, c)
    return {"embed": {"tokens": jax.random.normal(ke, (v, d), F32)
                      / np.sqrt(d),
                      "unembed": dense_init(jax.random.fold_in(ke, 1),
                                            (d, v))},
            "final_norm": jnp.zeros((d,), F32),
            "stages": {"s0": stage}}


def rope(x, theta):
    """x (S, H, D), rotate-half form."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, c, mm):
    s = x.shape[0]
    h, kvh, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    q = rope(mm(x, p["wq"]).reshape(s, h, hd), c["rope_theta"])
    k = rope(mm(x, p["wk"]).reshape(s, kvh, hd), c["rope_theta"])
    v = mm(x, p["wv"]).reshape(s, kvh, hd)
    k = jnp.repeat(k, h // kvh, 1)
    v = jnp.repeat(v, h // kvh, 1)
    qb = min(Q_BLOCK, s)
    pos = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, qpos = args                                   # (qb, H, D), (qb,)
        sc = mm.einsum("qhd,khd->hqk", qi, k) / np.sqrt(hd)
        back = qpos[None, :, None] - pos[None, None, :]
        sc = jnp.where((back >= 0) & (back <= c["sliding_window"]), sc,
                       -jnp.inf)
        pr = jax.nn.softmax(sc, -1)
        return mm.einsum("hqk,khd->qhd", pr, v)

    out = jax.lax.map(block, (q.reshape(s // qb, qb, h, hd),
                              pos.reshape(s // qb, qb)))
    return mm(out.reshape(s, h * hd), p["wo"])


def _ffn(p, x, mm):
    return mm(silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def block_params(params, rep, pi):
    bp = params["stages"]["s0"][f"pos{pi}"]
    if rep is None:
        return bp
    return jax.tree_util.tree_map(lambda a: a[rep], bp)


def hidden_row(params, tokens, c, mm):
    x = params["embed"]["tokens"][tokens] * np.sqrt(c["d_model"])
    for rep, pi, _ in layers(c):
        bp = block_params(params, rep, pi)

        def blk(x, bp=bp):
            x = x + _attention(bp["mixer"],
                               rms_norm(x, bp["mixer_norm"], c["norm_eps"]),
                               c, mm)
            return x + _ffn(bp["ffn"],
                            rms_norm(x, bp["ffn_norm"], c["norm_eps"]), mm)
        x = jax.checkpoint(blk)(x)
    return rms_norm(x, params["final_norm"], c["norm_eps"])


def logits_row(params, tokens, c, mm):
    return mm(hidden_row(params, tokens, c, mm), params["embed"]["unembed"])


# ----------------------------------------------------------------------
# work, from the sizes (model FLOPs; recomputation not counted)
# ----------------------------------------------------------------------
def matmul_params(c) -> int:
    """Weights a token meets in matrix products, the head included (the
    embedding lookup is not a product)."""
    d, hd = c["d_model"], c["head_dim"]
    q, kv = c["n_heads"] * hd, c["n_kv_heads"] * hd
    per_layer = d * (q + 2 * kv) + q * d + 3 * d * c["d_ff"]
    return d * c["vocab_size"] + per_layer * len(layers(c))


def mixer_flops_per_token(c, seq: int) -> float:
    """Forward operations of attention beyond the weight products (scores
    and their weighted sum, 4 per key and head dimension), averaged over a
    row of ``seq`` tokens: each query meets the keys at or before it,
    at most ``sliding_window`` back, counted once."""
    w = c["sliding_window"] + 1
    keys = sum(min(i + 1, w) for i in range(seq)) / seq
    return 4 * keys * c["n_heads"] * c["head_dim"] * len(layers(c))
