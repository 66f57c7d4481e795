"""Serving runtime: continuous batching, compressed-form execution,
cache padding, and the LC→serving checkpoint bridge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerSpec, MLACfg, MambaCfg, ModelConfig
from repro.core import AsIs, AsVector, CompressionTask, LCAlgorithm
from repro.core.schemes import (
    AdaptiveQuantization, ConstraintL0Pruning, LowRank)
from repro.kernels.lowrank import serve as lowrank_serve
from repro.kernels.prune import serve as prune_serve
from repro.kernels.quant_matmul import ops as qops
from repro.models.transformer import (
    decode_step, forward_hidden, init_cache, init_params)
from repro.models.layers import unembed
from repro.runtime import compressed as cforms
from repro.runtime.server import (
    Request, Server, ServingEngine, densified_for_serving,
    load_compressed_for_serving, pad_caches_to, sample_tokens)

KP = jax.random.PRNGKey(0)


def tiny_cfg(*specs, **kw):
    base = dict(name="t", d_model=32, n_heads=2, n_kv_heads=2,
                head_dim=8, d_ff=64, vocab_size=128,
                pattern=tuple(specs), pattern_reps=1,
                attn_chunk_q=4, attn_chunk_kv=4, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def gold_decode(cfg, params, prompt, n_new, max_len):
    """Independent reference: scalar-position decode loop from scratch."""
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg))
    cache = init_cache(cfg, 1, max_len)
    for i, t in enumerate(prompt):
        logits, cache = step(params, cache,
                             jnp.asarray([[t]], jnp.int32), jnp.int32(i))
    out = [int(jnp.argmax(logits[0, 0]))]
    pos = len(prompt)
    while len(out) < n_new:
        logits, cache = step(params, cache,
                             jnp.asarray([[out[-1]]], jnp.int32),
                             jnp.int32(pos))
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return np.asarray(out, np.int32)


# ----------------------------------------------------------------------
# Serving kernels
# ----------------------------------------------------------------------
@pytest.mark.parametrize("k", [16, 17])           # even + odd rows
def test_pack4_roundtrip(k):
    idx = jax.random.randint(KP, (k, 24), 0, 16).astype(jnp.uint8)
    packed = qops.pack4(idx)
    assert packed.shape == ((k + 1) // 2, 24)
    assert np.array_equal(np.asarray(qops.unpack4(packed))[:k], idx)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_matmul_packed_vs_dequant(use_pallas):
    kx, kw, kc = jax.random.split(KP, 3)
    m, k, n = 5, 32, 24
    x = jax.random.normal(kx, (m, k), jnp.float32)
    cb = jnp.sort(jax.random.normal(kc, (16,)))
    idx = qops.pack_quantized(jax.random.normal(kw, (k, n)), cb)
    y = qops.matmul_packed(x, qops.pack4(idx), cb,
                           use_pallas=use_pallas)
    gold = x @ cb[idx.astype(jnp.int32)]
    np.testing.assert_allclose(np.asarray(y), np.asarray(gold),
                               rtol=1e-5, atol=1e-5)


def test_lowrank_matmul_never_materializes():
    kx, ku, kv = jax.random.split(KP, 3)
    x = jax.random.normal(kx, (3, 16))
    u = jax.random.normal(ku, (16, 4))
    vt = jax.random.normal(kv, (4, 12))
    np.testing.assert_allclose(
        np.asarray(lowrank_serve.lowrank_matmul(x, u, vt)),
        np.asarray(x @ (u @ vt)), rtol=1e-5, atol=1e-5)


def test_sparse_matmul_matches_dense():
    kx, kw = jax.random.split(KP)
    x = jax.random.normal(kx, (3, 16))
    w = np.array(jax.random.normal(kw, (16, 12)))
    w[np.abs(w) < 0.8] = 0.0
    rows, cols = np.nonzero(w)
    y = prune_serve.sparse_matmul(
        x, jnp.asarray(w[rows, cols]), jnp.asarray(rows, jnp.int32),
        jnp.asarray(cols, jnp.int32), 12)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ w,
                               rtol=1e-5, atol=1e-5)
    dense = prune_serve.densify(
        jnp.asarray(w[rows, cols]), jnp.asarray(rows, jnp.int32),
        jnp.asarray(cols, jnp.int32), w.shape)
    assert np.array_equal(np.asarray(dense), w)


# ----------------------------------------------------------------------
# pad_caches_to
# ----------------------------------------------------------------------
def _prefill_pad_decode(cfg, s, max_len, n_new):
    """Prefill s tokens, pad caches, decode n_new — must match the
    scalar decode-from-scratch gold."""
    params = init_params(KP, cfg)
    prompt = np.asarray(
        jax.random.randint(KP, (s,), 1, cfg.vocab_size), np.int32)
    hidden, _, caches = forward_hidden(params, jnp.asarray(prompt)[None],
                                       cfg, return_caches=True)
    logits = unembed(params["embed"], hidden[:, -1:], cfg)
    caches = pad_caches_to(caches, cfg, s, max_len)
    out = [int(jnp.argmax(logits[0, 0]))]
    for i in range(n_new - 1):
        logits, caches = decode_step(
            params, caches, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.int32(s + i), cfg)
        out.append(int(jnp.argmax(logits[0, 0])))
    gold = gold_decode(cfg, params, prompt, n_new, max_len)
    assert np.array_equal(np.asarray(out, np.int32), gold)


def test_pad_caches_windowed_ring_roll():
    # cur_len (8) > window (4): the ring must be rolled so position p
    # stays at slot p % window across the prefill→decode handoff
    cfg = tiny_cfg(LayerSpec("attn", "dense", window=4))
    _prefill_pad_decode(cfg, s=8, max_len=16, n_new=5)


def test_pad_caches_mla_seq_padding():
    cfg = tiny_cfg(LayerSpec("mla", "dense"),
                   mla=MLACfg(q_lora_rank=16, kv_lora_rank=8,
                              qk_nope_dim=8, qk_rope_dim=8,
                              v_head_dim=8))
    _prefill_pad_decode(cfg, s=8, max_len=16, n_new=5)


def test_pad_caches_recurrent_passthrough():
    cfg = tiny_cfg(LayerSpec("mamba", "dense"),
                   mamba=MambaCfg(d_state=4, d_conv=4, expand=2,
                                  dt_rank=8))
    params = init_params(KP, cfg)
    x = jax.random.randint(KP, (1, 8), 1, cfg.vocab_size)
    _, _, caches = forward_hidden(params, x, cfg, return_caches=True)
    padded = pad_caches_to(caches, cfg, 8, 32)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        caches, padded)


# ----------------------------------------------------------------------
# Continuous batching engine
# ----------------------------------------------------------------------
def test_sample_tokens_greedy_matches_argmax():
    logits = jax.random.normal(KP, (4, 32))
    assert np.array_equal(
        np.asarray(sample_tokens(logits, KP, 0.0)),
        np.asarray(jnp.argmax(logits, -1)))


def test_engine_matches_scalar_decode_mixed_lengths():
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 127, size=s).astype(np.int32)
               for s in (3, 7, 12, 5)]
    max_news = [4, 6, 3, 5]
    gold = [gold_decode(cfg, params, p, m, 32)
            for p, m in zip(prompts, max_news)]

    eng = ServingEngine(cfg, params, slots=2, max_len=32,
                        prefill_chunk=4)
    reqs = [Request(id=i, prompt=p, max_new=m, arrival=0.0)
            for i, (p, m) in enumerate(zip(prompts, max_news))]
    out = eng.run(reqs)
    fin = {f.id: f.tokens for f in out["finished"]}
    assert len(fin) == len(reqs)
    for i, g in enumerate(gold):
        assert np.array_equal(fin[i], g), i
    # zero recompiles across the mixed-length trace
    assert all(n == 1 for n in eng.trace_counts.values()), \
        eng.trace_counts


def test_engine_rejects_oversized_and_empty():
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    eng = ServingEngine(cfg, params, slots=2, max_len=16,
                        prefill_chunk=4)
    reqs = [
        Request(id=0, prompt=np.arange(1, 4, dtype=np.int32), max_new=2),
        Request(id=1, prompt=np.arange(1, 30, dtype=np.int32),
                max_new=10),                      # 29 + 10 > 16
        Request(id=2, prompt=np.asarray([], np.int32), max_new=2),
    ]
    out = eng.run(reqs)
    assert sorted(r.id for r in out["rejected"]) == [1, 2]
    assert [f.id for f in out["finished"]] == [0]


def test_server_generate_in_jit_sampling():
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    srv = Server(cfg, params, max_len=32)
    prompt = np.asarray(
        jax.random.randint(KP, (8,), 1, cfg.vocab_size), np.int32)
    res = srv.generate(jnp.asarray(prompt)[None], 6)
    gold = gold_decode(cfg, params, prompt, 6, 32)
    assert np.array_equal(res.tokens[0], gold)
    # temperature sampling is deterministic under a fixed key
    a = srv.generate(jnp.asarray(prompt)[None], 6, temperature=0.8,
                     key=jax.random.PRNGKey(7))
    b = srv.generate(jnp.asarray(prompt)[None], 6, temperature=0.8,
                     key=jax.random.PRNGKey(7))
    assert np.array_equal(a.tokens, b.tokens)


# ----------------------------------------------------------------------
# LC checkpoint bridge + compressed-form parity
# ----------------------------------------------------------------------
def _bridge(cfg, params, task):
    algo = LCAlgorithm([task], [1e-4])
    state = algo.init(params)
    serving, report = load_compressed_for_serving(params, state,
                                                  algo.tasks)
    reference = densified_for_serving(params, state, algo.tasks)
    return serving, reference, report


def test_bridge_selects_all_three_forms():
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    _, _, rq = _bridge(cfg, params, CompressionTask(
        "q", r"ffn/w_gate", AsVector(), AdaptiveQuantization(k=16)))
    assert all(v == "quant4" for f in rq.values() for v in f.values())
    _, _, rl = _bridge(cfg, params, CompressionTask(
        "lr", r"ffn/w_up", AsIs(), LowRank(4)))
    assert all(v.startswith("lowrank") for f in rl.values()
               for v in f.values())
    _, _, rp = _bridge(cfg, params, CompressionTask(
        "pr", r"ffn/w_down", AsVector(), ConstraintL0Pruning(kappa=400)))
    assert all(v.startswith("sparse") for f in rp.values()
               for v in f.values())


def test_quantized_parity_tokens_and_logits():
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    serving, reference, _ = _bridge(cfg, params, CompressionTask(
        "q", r"ffn/w_", AsVector(), AdaptiveQuantization(k=16)))

    # logits parity on one decode step from a fresh cache
    tok = jnp.asarray([[5]], jnp.int32)
    lc_, _ = decode_step(serving, init_cache(cfg, 1, 16), tok,
                         jnp.int32(0), cfg)
    ld_, _ = decode_step(reference, init_cache(cfg, 1, 16), tok,
                         jnp.int32(0), cfg)
    np.testing.assert_allclose(np.asarray(lc_), np.asarray(ld_),
                               rtol=1e-4, atol=1e-4)

    # greedy-token parity over a full generation
    prompt = np.asarray(
        jax.random.randint(KP, (6,), 1, cfg.vocab_size), np.int32)
    assert np.array_equal(gold_decode(cfg, serving, prompt, 8, 32),
                          gold_decode(cfg, reference, prompt, 8, 32))


@pytest.mark.parametrize("task", [
    CompressionTask("lr", r"ffn/w_", AsIs(), LowRank(6)),
    CompressionTask("pr", r"ffn/w_", AsVector(),
                    ConstraintL0Pruning(kappa=1500)),
], ids=["lowrank", "sparse"])
def test_compressed_engine_parity(task):
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    serving, reference, _ = _bridge(cfg, params, task)
    rng = np.random.default_rng(3)
    reqs = [Request(id=i,
                    prompt=rng.integers(1, 127, size=s).astype(np.int32),
                    max_new=4, arrival=0.0)
            for i, s in enumerate((5, 9))]
    fc = {f.id: f.tokens for f in ServingEngine(
        cfg, serving, slots=2, max_len=32,
        prefill_chunk=4).run(list(reqs))["finished"]}
    fd = {f.id: f.tokens for f in ServingEngine(
        cfg, reference, slots=2, max_len=32,
        prefill_chunk=4).run(list(reqs))["finished"]}
    for i in fc:
        assert np.array_equal(fc[i], fd[i]), i


def test_hbm_accounting_orders_forms():
    cfg = tiny_cfg(LayerSpec("attn", "dense"))
    params = init_params(KP, cfg)
    qs, _, _ = _bridge(cfg, params, CompressionTask(
        "q", r"ffn/w_", AsVector(), AdaptiveQuantization(k=16)))
    dense_bytes = cforms.tree_weight_bytes(params)
    quant_bytes = cforms.tree_weight_bytes(qs)
    assert quant_bytes < dense_bytes
    # 4-bit packing: the ffn matrices shrink 4x vs bf16 modeling
    w = params["stages"]["s0"]["pos0"]["ffn"]["w_gate"]
    assert cforms.weight_form_bytes(
        qs["stages"]["s0"]["pos0"]["ffn"]["w_gate"]) < w.size


def test_xlstm_quant4_engine_reuses_slots():
    """The quant4 form of xLSTM keeps per-layer (unstacked) caches, so the
    sLSTM cache leaves reach the engine's donated reset program as they
    were made: each must be its own buffer. More requests than slots
    force every slot to be reset and reused."""
    from repro.configs import get_config, reduced_config
    from repro.launch.serve import compress_for_form
    from repro.models.ssm import init_slstm_cache

    cfg = reduced_config(get_config("xlstm-125m")).with_(pattern_reps=1)
    spec = next(s for s in cfg.pattern if s.mixer == "slstm")
    leaves = init_slstm_cache(cfg, spec, 2, 8, jnp.float32).values()
    assert len({id(x) for x in leaves}) == len(leaves)

    params = init_params(KP, cfg)
    serving, _ = compress_for_form(cfg, params, "quant4")
    rng = np.random.default_rng(1)
    reqs = [Request(id=i,
                    prompt=rng.integers(1, cfg.vocab_size, size=s)
                    .astype(np.int32),
                    max_new=3, arrival=0.0)
            for i, s in enumerate((4, 9, 6, 11, 5))]
    eng = ServingEngine(cfg, serving, slots=2, max_len=24, prefill_chunk=4)
    out = eng.run(reqs)
    assert not out["rejected"]
    assert sorted(f.id for f in out["finished"]) == list(range(len(reqs)))
    assert all(len(f.tokens) == 3 for f in out["finished"])
    assert all(n == 1 for n in eng.trace_counts.values()), eng.trace_counts
