"""Attention mixers: GQA (full/sliding-window) and MLA, with blockwise
(FlashAttention-style online-softmax) training/prefill and 1-token decode
against full or ring-buffer KV caches.

Memory discipline: the (S, S) logit matrix is never materialized — the
blockwise path scans q-chunks × kv-chunks keeping (m, l, acc) running
statistics, so peak attention memory is O(B·H·qc·kc) regardless of S.
This is what lets prefill_32k compile inside 16 GB/chip.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from repro.models.layers import (apply_rope, apply_w, dense_init, rms_norm,
                                 wload)

NEG_INF = -1e30


# ======================================================================
# GQA / sliding-window attention
# ======================================================================
def init_attn(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return {
        "wq": dense_init(ks[0], (d, q)),
        "wk": dense_init(ks[1], (d, kv)),
        "wv": dense_init(ks[2], (d, kv)),
        "wo": dense_init(ks[3], (q, d)),
    }


def attn_axes(cfg) -> dict:
    return {
        "wq": ("embed", "heads_flat"),
        "wk": ("embed", "kv_flat"),
        "wv": ("embed", "kv_flat"),
        "wo": ("heads_flat", "embed"),
    }


def read_layer_cache(cache: dict, layer_idx) -> dict:
    """Slice one layer's state out of a layer-stacked cache dict."""
    return {k: jax.lax.dynamic_index_in_dim(v, layer_idx, 0,
                                            keepdims=False)
            for k, v in cache.items()}


def write_layer_cache(cache: dict, new: dict, layer_idx) -> dict:
    """Write one layer's (full) state back into the stacked buffer."""
    out = {}
    zero = jnp.int32(0)
    for k, v in cache.items():
        idx = (layer_idx,) + (zero,) * (v.ndim - 1)
        out[k] = jax.lax.dynamic_update_slice(
            v, new[k][None].astype(v.dtype), idx)
    return out


def _mask(q_pos, k_pos, window: int):
    """Causal (+ sliding-window) mask: True = attend."""
    ok = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return ok


def blockwise_attention(q, k, v, q_positions, k_positions, *,
                        window: int = 0, q_chunk: int = 1024,
                        kv_chunk: int = 1024, scale: float | None = None,
                        fused: bool = False):
    """Online-softmax attention.

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D); positions: (Sq,), (Sk,).
    Returns (B, Sq, H, D). Causal by construction of the position mask.

    The gradient is FlashAttention-2's: the forward saves q, k, v, the
    output and the per-row logsumexp, and the backward recomputes each
    chunk pair's logits and probabilities (``_attention_bwd``), so no
    (qc, kc) array is kept between the passes. Every op of both passes
    runs under the ``blockwise_attention`` named scope.

    ``fused=True`` tags the computation as the fused flash-attention
    Pallas kernel (kernels/flash_attention — same math, VMEM-resident
    tiles) for the dry-run's fused-kernel byte accounting.
    """
    if fused:
        with jax.named_scope("fused_flash_attention"):
            return blockwise_attention(
                q, k, v, q_positions, k_positions, window=window,
                q_chunk=q_chunk, kv_chunk=kv_chunk, scale=scale,
                fused=False)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qc = min(q_chunk, q.shape[1])
    kc = min(kv_chunk, k.shape[1])
    assert q.shape[1] % qc == 0 and k.shape[1] % kc == 0, \
        (q.shape[1], qc, k.shape[1], kc)
    return _attention(window, qc, kc, scale, q, k, v, q_positions,
                      k_positions)


def _chunk_q(x, qc):
    """(B, Sq, KV, G, D) -> (nq, B, qc, KV, G, D): scan axis first."""
    b, sq, kv, g, d = x.shape
    return jnp.moveaxis(x.reshape(b, sq // qc, qc, kv, g, d), 1, 0)


def _chunk_kv(x, kc):
    """(B, Sk, KV, D) -> (nk, B, kc, KV, D)."""
    b, sk, kv, d = x.shape
    return jnp.moveaxis(x.reshape(b, sk // kc, kc, kv, d), 1, 0)


def _logits(q_i, k_j, qp_i, kp_j, window, scale):
    """One chunk pair's masked logits (B, KV, G, qc, kc), f32."""
    s = jnp.einsum("bqkgd,bckd->bkgqc", q_i, k_j,
                   preferred_element_type=jnp.float32) * scale
    mask = _mask(qp_i, kp_j, window)                      # (qc, kc)
    return jnp.where(mask[None, None, None], s, NEG_INF)


def _attention_fwd_scan(window, qc, kc, scale, q, k, v,
                        q_positions, k_positions):
    """The online-softmax scan: (output (B, Sq, H, Dv), logsumexp
    (nq, B, KV, G, qc) f32)."""
    with jax.named_scope("blockwise_attention"):
        b, sq, h, d = q.shape
        sk, kv = k.shape[1], k.shape[2]
        dv = v.shape[-1]                      # may differ from d (MLA)
        g = h // kv
        qr = _chunk_q(q.reshape(b, sq, kv, g, d), qc)
        kr, vr = _chunk_kv(k, kc), _chunk_kv(v, kc)
        qp = q_positions.reshape(sq // qc, qc)
        kp = k_positions.reshape(sk // kc, kc)

        def q_chunk_body(_, qi):
            q_i, qp_i = qi                       # (B,qc,KV,G,D), (qc,)
            m0 = jnp.full((b, kv, g, qc), NEG_INF, jnp.float32)
            l0 = jnp.zeros((b, kv, g, qc), jnp.float32)
            a0 = jnp.zeros((b, kv, g, qc, dv), jnp.float32)

            def kv_chunk_body(carry, kj):
                m, l, acc = carry
                k_j, v_j, kp_j = kj
                s = _logits(q_i, k_j, qp_i, kp_j, window, scale)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(v_j.dtype),
                                v_j, preferred_element_type=jnp.float32)
                acc_new = acc * alpha[..., None] + pv
                return (m_new, l_new, acc_new), None

            (m, l, acc), _ = jax.lax.scan(
                kv_chunk_body, (m0, l0, a0), (kr, vr, kp))
            out = acc / jnp.maximum(l[..., None], 1e-30)   # (B,KV,G,qc,D)
            return None, (out.astype(q.dtype), m + jnp.log(l))

        _, (outs, lse) = jax.lax.scan(q_chunk_body, None, (qr, qp))
        out = jnp.moveaxis(outs, 0, 1)                  # (B,nq,KV,G,qc,Dv)
        out = jnp.moveaxis(out, 4, 2)                   # (B,nq,qc,KV,G,Dv)
        return out.reshape(b, sq, h, dv), lse


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _attention(window, qc, kc, scale, q, k, v, q_positions, k_positions):
    return _attention_fwd_scan(window, qc, kc, scale, q, k, v,
                               q_positions, k_positions)[0]


def _attention_fwd(window, qc, kc, scale, q, k, v, q_positions,
                   k_positions):
    out, lse = _attention_fwd_scan(window, qc, kc, scale, q, k, v,
                                   q_positions, k_positions)
    return out, (q, k, v, out, lse, q_positions, k_positions)


def _attention_bwd(window, qc, kc, scale, res, d_out):
    """FlashAttention-2's backward. Per chunk pair: p = exp(s - lse),
    dV += pᵀ·dO, dS = p ⊙ (dO·Vᵀ - D) with D = rowsum(dO ⊙ O),
    dQ += dS·K, dK += dSᵀ·Q. The outer scan runs over kv chunks and
    carries dQ; the inner one runs over q chunks and carries the kv
    chunk's dK and dV."""
    with jax.named_scope("blockwise_attention"):
        q, k, v, out, lse, q_positions, k_positions = res
        b, sq, h, d = q.shape
        sk, kv = k.shape[1], k.shape[2]
        dv = v.shape[-1]
        g = h // kv
        nq = sq // qc
        qr = _chunk_q(q.reshape(b, sq, kv, g, d), qc)
        dor = _chunk_q(d_out.reshape(b, sq, kv, g, dv), qc)
        # D (nq, B, KV, G, qc): the rows' dO·O, as lse is laid out
        dsum = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1)
        dsum = dsum.reshape(b, nq, qc, kv, g).transpose(1, 0, 3, 4, 2)
        kr, vr = _chunk_kv(k, kc), _chunk_kv(v, kc)
        qp = q_positions.reshape(nq, qc)
        kp = k_positions.reshape(sk // kc, kc)

        def kv_chunk_body(dq, kj):
            k_j, v_j, kp_j = kj

            def q_chunk_body(carry, qi):
                dk_j, dv_j = carry
                q_i, do_i, lse_i, d_i, qp_i = qi
                s = _logits(q_i, k_j, qp_i, kp_j, window, scale)
                p = jnp.exp(s - lse_i[..., None])
                dv_j = dv_j + jnp.einsum(
                    "bkgqc,bqkgd->bckd", p.astype(v_j.dtype), do_i,
                    preferred_element_type=jnp.float32)
                dp = jnp.einsum("bqkgd,bckd->bkgqc", do_i, v_j,
                                preferred_element_type=jnp.float32)
                ds = (p * (dp - d_i[..., None])).astype(q_i.dtype)
                dk_j = dk_j + jnp.einsum(
                    "bkgqc,bqkgd->bckd", ds, q_i,
                    preferred_element_type=jnp.float32) * scale
                dq_i = jnp.einsum("bkgqc,bckd->bqkgd", ds, k_j,
                                  preferred_element_type=jnp.float32) * scale
                return (dk_j, dv_j), dq_i

            zero = (jnp.zeros((b, kc, kv, d), jnp.float32),
                    jnp.zeros((b, kc, kv, dv), jnp.float32))
            (dk_j, dv_j), dq_j = jax.lax.scan(
                q_chunk_body, zero, (qr, dor, lse, dsum, qp))
            return dq + dq_j, (dk_j.astype(k.dtype), dv_j.astype(v.dtype))

        dq0 = jnp.zeros(qr.shape, jnp.float32)
        dq, (dk, dv_) = jax.lax.scan(kv_chunk_body, dq0, (kr, vr, kp))
        dq = jnp.moveaxis(dq, 0, 1).reshape(b, sq, h, d).astype(q.dtype)
        dk = jnp.moveaxis(dk, 0, 1).reshape(b, sk, kv, d)
        dv_ = jnp.moveaxis(dv_, 0, 1).reshape(b, sk, kv, dv)
        return dq, dk, dv_, None, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def attn_forward(params, x, cfg, spec, positions, return_cache=False):
    """Full-sequence attention (train / prefill). x: (B, S, d_model)."""
    b, s, _ = x.shape
    dt = x.dtype
    q = apply_w(x, params["wq"], dt).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    k = apply_w(x, params["wk"], dt).reshape(
        b, s, cfg.n_kv_heads, cfg.head_dim)
    v = apply_w(x, params["wv"], dt).reshape(
        b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    out = blockwise_attention(
        q, k, v, positions, positions, window=spec.window,
        q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv,
        fused=cfg.fused_attention)
    out = constrain(out, ("batch", "seq", "heads", None))
    y = apply_w(out.reshape(b, s, cfg.q_dim), params["wo"], dt)
    if not return_cache:
        return y
    w = spec.window
    if w > 0 and s > w:  # ring-buffer layers keep the last window
        k, v = k[:, -w:], v[:, -w:]
    return y, {"k": k, "v": v}


# ----------------------------------------------------------------------
# Decode path (1 new token against a KV cache)
# ----------------------------------------------------------------------
def init_attn_cache(cfg, spec, batch: int, max_len: int, dtype) -> dict:
    """Full cache for global layers; ring buffer for windowed layers."""
    length = min(spec.window, max_len) if spec.window > 0 else max_len
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_decode(params, x, cache, pos, cfg, spec, layer_idx=None):
    """x: (B, 1, d_model); pos: 0-based index of the new token — scalar
    int32 (whole batch in lockstep) or (B,) int32 (per-slot positions,
    continuous batching: every slot writes its own ring slot and masks
    its own validity range).

    ``layer_idx`` set ⇒ cache leaves are layer-stacked (L, B, len, KV, D)
    and this layer's update is a single token-sized dynamic-update-slice
    into the shared (donated) buffer — decode writes O(token), never
    O(cache). With layer_idx=None (unrolled stages) the per-layer cache
    is updated functionally as before.
    """
    b = x.shape[0]
    dt = x.dtype
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    q = apply_w(x, params["wq"], dt).reshape(
        b, 1, cfg.n_heads, cfg.head_dim)
    k = apply_w(x, params["wk"], dt).reshape(
        b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = apply_w(x, params["wv"], dt).reshape(
        b, 1, cfg.n_kv_heads, cfg.head_dim)
    # (1,) broadcasts over batch; (B, 1) gives each slot its own angle
    pos_arr = pos[:, None] if per_slot else jnp.reshape(pos, (1,))
    q = apply_rope(q, pos_arr, cfg.rope_theta)
    k = apply_rope(k, pos_arr, cfg.rope_theta)

    stacked = layer_idx is not None
    k_buf, v_buf = cache["k"], cache["v"]
    length = k_buf.shape[2] if stacked else k_buf.shape[1]
    slot = jnp.where(spec.window > 0, pos % length,
                     jnp.minimum(pos, length - 1)).astype(jnp.int32)
    if per_slot:
        rows = jnp.arange(b)
        if stacked:
            k_buf = k_buf.at[layer_idx, rows, slot].set(
                k[:, 0].astype(k_buf.dtype))
            v_buf = v_buf.at[layer_idx, rows, slot].set(
                v[:, 0].astype(v_buf.dtype))
            with jax.named_scope("fused_flash_attention"
                                 if cfg.fused_attention else "cache_read"):
                k_cache = jax.lax.dynamic_index_in_dim(
                    k_buf, layer_idx, 0, keepdims=False)
                v_cache = jax.lax.dynamic_index_in_dim(
                    v_buf, layer_idx, 0, keepdims=False)
        else:
            k_cache = k_buf.at[rows, slot].set(k[:, 0].astype(k_buf.dtype))
            v_cache = v_buf.at[rows, slot].set(v[:, 0].astype(v_buf.dtype))
            k_buf, v_buf = k_cache, v_cache
    elif stacked:
        zero = jnp.int32(0)
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, k[None].astype(k_buf.dtype),
            (layer_idx, zero, slot, zero, zero))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, v[None].astype(v_buf.dtype),
            (layer_idx, zero, slot, zero, zero))
        # the layer-cache read is part of the flash-decoding kernel's
        # streaming loop; keep it inside the fused scope
        with jax.named_scope("fused_flash_attention"
                             if cfg.fused_attention else "cache_read"):
            k_cache = jax.lax.dynamic_index_in_dim(
                k_buf, layer_idx, 0, keepdims=False)
            v_cache = jax.lax.dynamic_index_in_dim(
                v_buf, layer_idx, 0, keepdims=False)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_buf, k.astype(k_buf.dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_buf, v.astype(v_buf.dtype), slot, axis=1)
        k_buf, v_buf = k_cache, v_cache

    kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    qh = q.reshape(b, kv, g, cfg.head_dim)

    def _core(qh, k_cache, v_cache):
        s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache,
                       preferred_element_type=jnp.float32)
        s = s / np.sqrt(cfg.head_dim)
        n_valid = jnp.minimum(pos + 1, length)      # () or (B,)
        n_valid = n_valid[:, None, None, None] if per_slot else n_valid
        valid = jnp.arange(length)[None, None, None, :] < n_valid
        s = jnp.where(valid, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                          preferred_element_type=jnp.float32).astype(dt)

    if cfg.fused_attention:  # flash-decoding kernel accounting
        with jax.named_scope("fused_flash_attention"):
            out = _core(qh, k_cache, v_cache)
    else:
        out = _core(qh, k_cache, v_cache)
    out = out.reshape(b, 1, cfg.q_dim)
    return apply_w(out, params["wo"], dt), {"k": k_buf, "v": v_buf}


# ======================================================================
# Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style)
# ======================================================================
def init_mla(key, cfg) -> dict:
    m = cfg.mla
    ks = jax.random.split(key, 5)
    h = cfg.n_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    return {
        "wdq": dense_init(ks[0], (cfg.d_model, m.q_lora_rank)),
        "q_norm": jnp.zeros((m.q_lora_rank,), jnp.float32),
        "wuq": dense_init(ks[1], (m.q_lora_rank, h * qk_dim)),
        "wdkv": dense_init(ks[2], (cfg.d_model,
                                   m.kv_lora_rank + m.qk_rope_dim)),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), jnp.float32),
        "wukv": dense_init(ks[3], (m.kv_lora_rank,
                                   h * (m.qk_nope_dim + m.v_head_dim))),
        "wo": dense_init(ks[4], (h * m.v_head_dim, cfg.d_model)),
    }


def mla_axes(cfg) -> dict:
    return {
        "wdq": ("embed", "lora"),
        "q_norm": ("lora",),
        "wuq": ("lora", "heads_flat"),
        "wdkv": ("embed", "lora"),
        "kv_norm": ("lora",),
        "wukv": ("lora", "heads_flat"),
        "wo": ("heads_flat", "embed"),
    }


def _mla_qkv(params, x, cfg, positions):
    """Shared q/k/v construction for the full-sequence MLA path."""
    m = cfg.mla
    b, s, _ = x.shape
    dt = x.dtype
    h = cfg.n_heads
    cq = rms_norm(apply_w(x, params["wdq"], dt), params["q_norm"],
                  cfg.norm_eps)
    q = apply_w(cq, params["wuq"], dt).reshape(
        b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = apply_w(x, params["wdkv"], dt)
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    ckv_n = rms_norm(ckv, params["kv_norm"], cfg.norm_eps)
    kv = (ckv_n @ wload(params["wukv"], dt)).reshape(
        b, s, h, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    return q, k, v, ckv_n, k_rope


def mla_forward(params, x, cfg, spec, positions, return_cache=False):
    m = cfg.mla
    b, s, _ = x.shape
    dt = x.dtype
    q, k, v, ckv_n, k_rope = _mla_qkv(params, x, cfg, positions)
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    out = blockwise_attention(
        q, k, v, positions, positions, window=spec.window,
        q_chunk=cfg.attn_chunk_q, kv_chunk=cfg.attn_chunk_kv, scale=scale,
        fused=cfg.fused_attention)
    out = out.reshape(b, s, cfg.n_heads * m.v_head_dim)
    y = apply_w(out, params["wo"], dt)
    if not return_cache:
        return y
    return y, {"ckv": ckv_n, "k_rope": k_rope[:, :, 0, :]}


def init_mla_cache(cfg, spec, batch: int, max_len: int, dtype) -> dict:
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
    }


def mla_decode(params, x, cache, pos, cfg, spec, layer_idx=None):
    """Absorbed-matrix MLA decode: attention runs in the latent space, so
    per-step work is O(S·(kv_lora+rope)) instead of O(S·H·qk_dim)."""
    m = cfg.mla
    b = x.shape[0]
    dt = x.dtype
    h = cfg.n_heads
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1            # (B,) continuous-batching positions
    pos_arr = pos[:, None] if per_slot else jnp.reshape(pos, (1,))
    stacked = layer_idx is not None

    cq = rms_norm(apply_w(x, params["wdq"], dt), params["q_norm"],
                  cfg.norm_eps)
    q = apply_w(cq, params["wuq"], dt).reshape(
        b, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope[:, None], pos_arr,
                        cfg.rope_theta)[:, 0]            # (B,H,rope)

    ckv_full = apply_w(x, params["wdkv"], dt)[:, 0]      # (B, lora+rope)
    ckv_new, k_rope_new = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv_new = rms_norm(ckv_new, params["kv_norm"], cfg.norm_eps)
    k_rope_new = apply_rope(k_rope_new[:, None, None, :], pos_arr,
                            cfg.rope_theta)[:, 0, 0]

    if per_slot:
        rows = jnp.arange(b)
        if stacked:
            ckv_buf = cache["ckv"].at[layer_idx, rows, pos].set(
                ckv_new.astype(cache["ckv"].dtype))
            kr_buf = cache["k_rope"].at[layer_idx, rows, pos].set(
                k_rope_new.astype(cache["k_rope"].dtype))
            with jax.named_scope("fused_flash_attention"
                                 if cfg.fused_attention else "cache_read"):
                ckv = jax.lax.dynamic_index_in_dim(ckv_buf, layer_idx, 0,
                                                   keepdims=False)
                k_rope = jax.lax.dynamic_index_in_dim(kr_buf, layer_idx, 0,
                                                      keepdims=False)
        else:
            ckv = cache["ckv"].at[rows, pos].set(
                ckv_new.astype(cache["ckv"].dtype))
            k_rope = cache["k_rope"].at[rows, pos].set(
                k_rope_new.astype(cache["k_rope"].dtype))
            ckv_buf, kr_buf = ckv, k_rope
    elif stacked:
        zero = jnp.int32(0)
        ckv_buf = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv_new[None, :, None].astype(
                cache["ckv"].dtype), (layer_idx, zero, pos, zero))
        kr_buf = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope_new[None, :, None].astype(
                cache["k_rope"].dtype), (layer_idx, zero, pos, zero))
        with jax.named_scope("fused_flash_attention"
                             if cfg.fused_attention else "cache_read"):
            ckv = jax.lax.dynamic_index_in_dim(ckv_buf, layer_idx, 0,
                                               keepdims=False)
            k_rope = jax.lax.dynamic_index_in_dim(kr_buf, layer_idx, 0,
                                                  keepdims=False)
    else:
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv_new[:, None].astype(cache["ckv"].dtype),
            pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"],
            k_rope_new[:, None].astype(cache["k_rope"].dtype),
            pos, axis=1)
        ckv_buf, kr_buf = ckv, k_rope

    # absorb W_uk into q: q_abs (B,H,lora)
    wukv = wload(params["wukv"], dt).reshape(
        m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim)
    w_uk = wukv[..., :m.qk_nope_dim]                     # (lora,H,nope)
    w_uv = wukv[..., m.qk_nope_dim:]                     # (lora,H,v)
    q_abs = jnp.einsum("bhn,lhn->bhl", q_nope, w_uk)

    def _core(q_abs, q_rope, ckv, k_rope):
        s = (jnp.einsum("bhl,bsl->bhs", q_abs, ckv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,bsr->bhs", q_rope, k_rope,
                          preferred_element_type=jnp.float32))
        s = s / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
        p_cmp = pos[:, None, None] if per_slot else pos
        valid = jnp.arange(ckv.shape[1])[None, None, :] <= p_cmp
        s = jnp.where(valid, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("bhs,bsl->bhl", p, ckv)        # (B,H,lora)

    if cfg.fused_attention:
        with jax.named_scope("fused_flash_attention"):
            o_latent = _core(q_abs, q_rope, ckv, k_rope)
    else:
        o_latent = _core(q_abs, q_rope, ckv, k_rope)
    out = jnp.einsum("bhl,lhv->bhv", o_latent, w_uv)     # (B,H,v)
    out = out.reshape(b, 1, h * m.v_head_dim)
    return apply_w(out, params["wo"], dt), {"ckv": ckv_buf,
                                            "k_rope": kr_buf}
