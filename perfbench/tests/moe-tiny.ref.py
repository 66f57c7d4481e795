"""Plain float32 reference of a decoder whose FFN is a mixture of experts,
kept for the tests: the Phi-3-mini reference's attention block
(``configs/phi3-mini-3.8b.ref.py``) followed by a routed SwiGLU MoE with
shared experts, as DeepSeekMoE (arXiv:2401.06066) lays it out.

Each token's router probabilities are a softmax over the experts; it
takes the ``top_k`` most likely, weighs them by their probabilities
renormalised over the chosen, and adds the shared experts' SwiGLU. Every
expert is applied to every token and weighed by its gate where chosen (0
elsewhere): no token is dropped. ``aux_loss`` is the Switch balance term
of each MoE layer over the whole batch (``refs.switch_balance``), summed
over the layers and weighted by the file's ``router_aux_weight``.
Weights are drawn from the seed as the program draws them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from refs import F32, dense_init, model_reference, rms_norm, silu, \
    switch_balance

PHI3 = model_reference("phi3-mini-3.8b")


def _init_moe(key, c):
    m = c["moe"]
    d, e, fe = c["d_model"], m["n_experts"], m["d_expert"]
    ks = jax.random.split(key, 7)

    def experts(k, shape):
        return jnp.stack([dense_init(kk, shape)
                          for kk in jax.random.split(k, e)])
    p = {"router": dense_init(ks[0], (d, e)),
         "w_gate": experts(ks[1], (d, fe)),
         "w_up": experts(ks[2], (d, fe)),
         "w_down": experts(ks[3], (fe, d))}
    if m["n_shared"]:
        fs = m["n_shared"] * fe
        p.update(sw_gate=dense_init(ks[4], (d, fs)),
                 sw_up=dense_init(ks[5], (d, fs)),
                 sw_down=dense_init(ks[6], (fs, d)))
    return p


def _init_block(key, c):
    blk = PHI3._init_block(key, c)
    blk["ffn"] = _init_moe(jax.random.split(key)[1], c)
    return blk


def init_params(key, c):
    d, v, reps = c["d_model"], c["vocab_size"], c["pattern_reps"]
    ke, _kh, ks = jax.random.split(key, 3)
    stage = {}
    for pi, _spec in enumerate(c["pattern"]):
        keys = jax.random.split(jax.random.fold_in(ks, pi), reps)
        stage[f"pos{pi}"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[_init_block(k, c) for k in keys])
    return {"embed": {"tokens": jax.random.normal(ke, (v, d), F32)
                      / np.sqrt(d),
                      "unembed": dense_init(jax.random.fold_in(ke, 1),
                                            (d, v))},
            "final_norm": jnp.zeros((d,), F32),
            "stages": {"s0": stage}}


def _moe(p, x, c, mm):
    """x (S, d) -> (y (S, d), router probabilities (S, E), chosen (S, k))."""
    m = c["moe"]
    e = m["n_experts"]
    probs = jax.nn.softmax(mm(x, p["router"]), -1)
    top, chosen = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jnp.where(chosen[:, :, None] == jnp.arange(e), top[:, :, None],
                             0.0), 1)                              # (S, E)
    h = silu(mm.einsum("sd,edf->esf", x, p["w_gate"])) \
        * mm.einsum("sd,edf->esf", x, p["w_up"])
    y = jnp.einsum("se,esd->sd", gate, mm.einsum("esf,efd->esd", h,
                                                  p["w_down"]))
    if m["n_shared"]:
        y = y + mm(silu(mm(x, p["sw_gate"])) * mm(x, p["sw_up"]),
                   p["sw_down"])
    return y, probs, chosen


def hidden_row(params, tokens, c, mm):
    """One row's final hidden states and each MoE layer's routing."""
    x = params["embed"]["tokens"][tokens] * np.sqrt(c["d_model"])
    routes = []
    for rep, pi, _ in PHI3.layers(c):
        bp = PHI3.block_params(params, rep, pi)

        def blk(x, bp=bp):
            x = x + PHI3._attention(
                bp["mixer"], rms_norm(x, bp["mixer_norm"], c["norm_eps"]),
                c, mm)
            y, probs, chosen = _moe(
                bp["ffn"], rms_norm(x, bp["ffn_norm"], c["norm_eps"]), c, mm)
            return x + y, (probs, chosen)
        x, route = jax.checkpoint(blk)(x)
        routes.append(route)
    return rms_norm(x, params["final_norm"], c["norm_eps"]), routes


def logits_row(params, tokens, c, mm):
    return mm(hidden_row(params, tokens, c, mm)[0],
              params["embed"]["unembed"])


def aux_loss(params, tokens, c, mm):
    """The weighted balance term over the batch ``tokens`` (B, S)."""
    m = c["moe"]
    _, routes = jax.vmap(lambda t: hidden_row(params, t, c, mm))(tokens)
    total = sum(switch_balance(probs.reshape(-1, m["n_experts"]),
                               chosen.reshape(-1, m["top_k"]),
                               m["n_experts"])
                for probs, chosen in routes)
    return m["router_aux_weight"] * total


def matmul_params(c) -> int:
    """Weights a token meets in matrix products: attention, the router,
    its ``top_k`` experts and the shared ones, and the head."""
    d, hd, m = c["d_model"], c["head_dim"], c["moe"]
    q, kv = c["n_heads"] * hd, c["n_kv_heads"] * hd
    ffn = d * m["n_experts"] \
        + 3 * d * m["d_expert"] * (m["top_k"] + m["n_shared"])
    per_layer = d * (q + 2 * kv) + q * d + ffn
    return d * c["vocab_size"] + per_layer * len(PHI3.layers(c))


def mixer_flops_per_token(c, seq: int) -> float:
    return PHI3.mixer_flops_per_token(c, seq)
