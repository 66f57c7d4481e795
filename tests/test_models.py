"""Model-stack tests: per-arch smoke (reduced configs), blockwise
attention vs naive oracle, chunked-vs-recurrent consistency, and the
prefill-cache ↔ decode equivalence that the serving runtime relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced_config
from repro.models import (
    count_params, decode_step, forward_hidden, init_cache, init_params,
    loss_fn, param_axes)
from repro.models.attention import blockwise_attention
from repro.runtime.server import pad_caches_to

KEY = jax.random.PRNGKey(0)
KI, KL, KP = jax.random.split(KEY, 3)


def _batch(cfg, b=2, s=16):
    if cfg.input_mode == "tokens":
        inputs = jax.random.randint(KI, (b, s), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(KI, (b, s, cfg.d_input))
    labels = jax.random.randint(KL, (b, s), 0, cfg.vocab_size)
    return {"inputs": inputs, "labels": labels}


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_train_step(arch):
    """Reduced same-family config: one *jitted* forward/backward on
    CPU, output shapes + no NaNs (assignment requirement)."""
    cfg = reduced_config(get_config(arch))
    params = init_params(KP, cfg)
    batch = _batch(cfg)
    step = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg)[0], has_aux=False))
    loss, grads = step(params, batch)
    assert np.isfinite(float(loss))
    gnorm = jax.tree_util.tree_reduce(
        lambda a, g: a + float(jnp.sum(g.astype(jnp.float32) ** 2)),
        grads, 0.0)
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_exposes_compressible_matrix_leaf(arch):
    """Every reduced config must expose ≥1 matrix-eligible leaf (the
    scenario matrix's low-rank/rank-select families need one) — from
    shapes only, no init."""
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.matrix_common import leaf_plan
    cfg = reduced_config(get_config(arch))
    plan = leaf_plan(cfg)
    matrix = [i for i in plan if i.kind == "matrix"]
    assert matrix, f"{arch}: no matrix-shaped compressible leaf"
    for i in matrix:
        assert len(i.item_shape) == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_decode_shapes(arch):
    cfg = reduced_config(get_config(arch))
    params = init_params(KP, cfg)
    cache = init_cache(cfg, 2, 32)
    batch = _batch(cfg, s=1)
    logits, new_cache = decode_step(params, cache, batch["inputs"],
                                    jnp.int32(0), cfg)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(new_cache)


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_param_axes_structure_matches(arch):
    """The logical-axes tree must mirror the params tree exactly —
    this is what the dry-run shardings are built from."""
    cfg = reduced_config(get_config(arch))
    shapes = jax.eval_shape(lambda: init_params(KP, cfg))
    axes = param_axes(cfg)
    is_axes_leaf = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(e, (str, type(None))) for e in x)
    flat_s = jax.tree_util.tree_leaves(shapes)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=is_axes_leaf)
    assert len(flat_s) == len(flat_a)
    for leaf, ax in zip(flat_s, jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda s, a: (s, a), shapes, axes,
                                   is_leaf=lambda x: is_axes_leaf(x)))):
        pass  # structure equality asserted via the zip above


def test_count_params_matches_actual():
    for arch in ("phi3-mini-3.8b", "jamba-v0.1-52b", "xlstm-125m"):
        cfg = reduced_config(get_config(arch))
        params = init_params(KP, cfg)
        actual = sum(l.size for l in jax.tree_util.tree_leaves(params))
        assert abs(actual - count_params(cfg)) / actual < 1e-6


# ----------------------------------------------------------------------
# Attention oracle
# ----------------------------------------------------------------------
def _naive_attn(q, k, v, window=0):
    b, s, h, d = q.shape
    kv = k.shape[2]
    qh = q.reshape(b, s, kv, h // kv, d)
    sc = jnp.einsum("bqkgd,bckd->bkgqc", qh, k) / np.sqrt(d)
    i = jnp.arange(s)
    mask = i[None, :] <= i[:, None]
    if window:
        mask &= i[None, :] > i[:, None] - window
    sc = jnp.where(mask[None, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, -1)
    o = jnp.einsum("bkgqc,bckd->bkgqd", p, v)
    return jnp.moveaxis(o, 3, 1).reshape(b, s, h, d)


ATTN_GRID = [
    (2, 64, 4, 2, 16, 0, 16, 16),
    (1, 128, 8, 8, 32, 24, 32, 16),
    (3, 96, 6, 3, 16, 7, 32, 32),
    (2, 32, 2, 1, 8, 0, 32, 8),
]


@pytest.mark.parametrize("b,s,h,kvh,d,w,qc,kc", ATTN_GRID)
def test_blockwise_attention_matches_naive(b, s, h, kvh, d, w, qc, kc):
    kq, kk, kv_ = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, kvh, d))
    v = jax.random.normal(kv_, (b, s, kvh, d))
    pos = jnp.arange(s)
    out = blockwise_attention(q, k, v, pos, pos, window=w,
                              q_chunk=qc, kv_chunk=kc)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_naive_attn(q, k, v, w)),
                               atol=2e-5)


def test_blockwise_attention_mla_vdim():
    """v head dim ≠ qk head dim (MLA)."""
    kq, kk, kv_ = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (2, 32, 4, 24))
    k = jax.random.normal(kk, (2, 32, 4, 24))
    v = jax.random.normal(kv_, (2, 32, 4, 8))
    pos = jnp.arange(32)
    out = blockwise_attention(q, k, v, pos, pos, q_chunk=8, kv_chunk=8)
    assert out.shape == (2, 32, 4, 8)


# ----------------------------------------------------------------------
# Prefill-cache ↔ decode equivalence (per mixer family)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("arch", [
    "phi3-mini-3.8b",    # full attention
    "gemma3-27b",        # sliding-window ring buffer + global
    "minicpm3-4b",       # MLA latent cache
    "jamba-v0.1-52b",    # mamba + attention + MoE
    "xlstm-125m",        # mLSTM + sLSTM recurrent states
])
def test_prefill_then_decode_matches_all_decode(arch):
    import dataclasses
    cfg = reduced_config(get_config(arch)).with_(dtype="float32")
    if cfg.moe:
        # no-drop capacity: decode routes 1 token at a time, so per-step
        # capacity drops differ from prefill's batch routing otherwise
        cfg = cfg.with_(moe=dataclasses.replace(
            cfg.moe, capacity_factor=8.0))
    params = init_params(KP, cfg)
    b, s, extra = 2, 24, 4
    max_len = s + extra
    toks = jax.random.randint(KI, (b, s), 0, cfg.vocab_size)

    # path A: prefill with cache capture, then decode `extra` tokens
    hidden, _, caches = forward_hidden(params, toks, cfg,
                                       return_caches=True)
    caches_a = pad_caches_to(caches, cfg, s, max_len)

    # path B: token-by-token decode from scratch
    caches_b = init_cache(cfg, b, max_len, jnp.float32)
    logits_b = None
    for i in range(s):
        logits_b, caches_b = decode_step(
            params, caches_b, toks[:, i:i + 1], jnp.int32(i), cfg)

    from repro.models.layers import unembed
    logits_a = unembed(params["embed"], hidden[:, -1:], cfg)
    np.testing.assert_allclose(np.asarray(logits_a),
                               np.asarray(logits_b),
                               rtol=2e-3, atol=2e-3)

    # continue decoding — caches must agree functionally
    tok = jnp.argmax(logits_a, axis=-1).astype(jnp.int32)
    for i in range(extra):
        la, caches_a = decode_step(params, caches_a, tok,
                                   jnp.int32(s + i), cfg)
        lb, caches_b = decode_step(params, caches_b, tok,
                                   jnp.int32(s + i), cfg)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-3, atol=2e-3)
        tok = jnp.argmax(la, axis=-1).astype(jnp.int32)
