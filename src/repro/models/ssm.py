"""Recurrent mixers: Mamba-1 (Jamba) and xLSTM (mLSTM + sLSTM).

Training paths are chunked so memory stays O(B·chunk·inner·state):
* Mamba: outer `lax.scan` over sequence chunks, inner associative scan,
  checkpointed per chunk.
* mLSTM: chunkwise-parallel form — intra-chunk quadratic (c×c) gate
  matrix + inter-chunk (C, n, m) running state with max-stabilization
  (the flash-attention-style combine of the xLSTM paper's appendix).
* sLSTM: inherently sequential (block-diagonal recurrence) — `lax.scan`
  over time, as the paper itself prescribes.

Decode paths are single-step recurrent updates; state size is
independent of context length (this is why xlstm/jamba run long_500k).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from repro.models.attention import read_layer_cache, write_layer_cache
from repro.models.layers import apply_w, dense_init, rms_norm, wload


# ======================================================================
# Mamba-1
# ======================================================================
def _mamba_dims(cfg):
    m = cfg.mamba
    d_inner = m.expand * cfg.d_model
    dt_rank = m.dt_rank or int(np.ceil(cfg.d_model / 16))
    return d_inner, dt_rank


def init_mamba(key, cfg) -> dict:
    m = cfg.mamba
    di, dtr = _mamba_dims(cfg)
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, m.d_state + 1, dtype=jnp.float32), (di, 1))
    return {
        "in_proj": dense_init(ks[0], (cfg.d_model, 2 * di)),
        "conv_w": dense_init(ks[1], (m.d_conv, di)),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": dense_init(ks[2], (di, dtr + 2 * m.d_state)),
        "dt_proj": dense_init(ks[3], (dtr, di)),
        "dt_bias": jnp.log(jnp.expm1(  # softplus⁻¹ of U(1e-3, 1e-1) mean
            jnp.full((di,), 0.01, jnp.float32))),
        "A_log": jnp.log(a),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[5], (di, cfg.d_model)),
    }


def mamba_axes(cfg) -> dict:
    return {
        "in_proj": ("embed", "inner"),
        "conv_w": ("conv", "inner"),
        "conv_b": ("inner",),
        "x_proj": ("inner", "lora"),
        "dt_proj": ("lora", "inner"),
        "dt_bias": ("inner",),
        "A_log": ("inner", "state"),
        "D": ("inner",),
        "out_proj": ("inner", "embed"),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv over seq. x: (B,S,C), w: (K,C).

    ``state``: (B, K-1, C) trailing inputs from the previous step (decode);
    returns (y, new_state).
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)          # (B, S+K-1, C)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(k))
    new_state = xp[:, -(k - 1):, :]
    return y + b[None, None, :], new_state


def _selective_scan_chunk(h0, dA, dBx):
    """Associative scan within a chunk. dA, dBx: (B, c, di, ds)."""
    def combine(a, b):
        a1, b1 = a
        a2, b2 = b
        return a1 * a2, b1 * a2 + b2

    aa, bb = jax.lax.associative_scan(combine, (dA, dBx), axis=1)
    h = aa * h0[:, None] + bb                        # (B, c, di, ds)
    return h, h[:, -1]


def mamba_forward(params, x, cfg, spec, positions, chunk: int = 128,
                  return_cache=False):
    """x: (B, S, d_model) → (B, S, d_model)."""
    m = cfg.mamba
    di, dtr = _mamba_dims(cfg)
    b, s, _ = x.shape
    dt_ = x.dtype

    xz = apply_w(x, params["in_proj"], dt_)
    xi, z = jnp.split(xz, 2, axis=-1)
    xi = constrain(xi, ("batch", "seq", "inner"))
    xi, conv_tail = _causal_conv(xi, wload(params["conv_w"], dt_),
                                 params["conv_b"].astype(dt_))
    xi = jax.nn.silu(xi)

    xdbl = apply_w(xi, params["x_proj"], dt_)
    dt_raw, b_ssm, c_ssm = jnp.split(xdbl, [dtr, dtr + m.d_state], axis=-1)
    dt = jax.nn.softplus(
        apply_w(dt_raw, params["dt_proj"], dt_)
        + params["dt_bias"].astype(dt_))             # (B,S,di)
    a = -jnp.exp(params["A_log"])                    # (di, ds) f32

    c = min(chunk, s)
    assert s % c == 0
    nc = s // c
    xi_c = xi.reshape(b, nc, c, di)
    dt_c = dt.reshape(b, nc, c, di).astype(jnp.float32)
    b_c = b_ssm.reshape(b, nc, c, m.d_state).astype(jnp.float32)
    c_c = c_ssm.reshape(b, nc, c, m.d_state)

    @jax.checkpoint
    def chunk_body(h, inp):
        xi_j, dt_j, b_j, c_j = inp                    # (B,c,·)
        da = jnp.exp(dt_j[..., None] * a[None, None])        # (B,c,di,ds)
        dbx = (dt_j * xi_j.astype(jnp.float32))[..., None] \
            * b_j[..., None, :]                              # (B,c,di,ds)
        hs, h_last = _selective_scan_chunk(h, da, dbx)
        y = jnp.einsum("bcds,bcs->bcd", hs, c_j.astype(jnp.float32))
        return h_last, y.astype(dt_)

    h0 = jnp.zeros((b, di, m.d_state), jnp.float32)
    h_last, ys = jax.lax.scan(
        chunk_body, h0,
        (jnp.moveaxis(xi_c, 1, 0), jnp.moveaxis(dt_c, 1, 0),
         jnp.moveaxis(b_c, 1, 0), jnp.moveaxis(c_c, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, di)
    y = y + xi * params["D"].astype(dt_)[None, None]
    y = y * jax.nn.silu(z)
    out = apply_w(y, params["out_proj"], dt_)
    if not return_cache:
        return out
    return out, {"conv": conv_tail, "ssm": h_last}


def init_mamba_cache(cfg, spec, batch: int, max_len: int, dtype) -> dict:
    m = cfg.mamba
    di, _ = _mamba_dims(cfg)
    return {
        "conv": jnp.zeros((batch, m.d_conv - 1, di), dtype),
        "ssm": jnp.zeros((batch, di, m.d_state), jnp.float32),
    }


def mamba_decode(params, x, cache, pos, cfg, spec, layer_idx=None):
    if layer_idx is not None:  # layer-stacked cache (scanned decode)
        local = read_layer_cache(cache, layer_idx)
        out, new_local = mamba_decode(params, x, local, pos, cfg, spec)
        return out, write_layer_cache(cache, new_local, layer_idx)
    """x: (B, 1, d_model) single-step recurrence."""
    m = cfg.mamba
    di, dtr = _mamba_dims(cfg)
    b = x.shape[0]
    dt_ = x.dtype

    xz = apply_w(x, params["in_proj"], dt_)
    xi, z = jnp.split(xz, 2, axis=-1)
    xi, conv_state = _causal_conv(
        xi, wload(params["conv_w"], dt_), params["conv_b"].astype(dt_),
        state=cache["conv"])
    xi = jax.nn.silu(xi)[:, 0]                       # (B, di)

    xdbl = apply_w(xi, params["x_proj"], dt_)
    dt_raw, b_ssm, c_ssm = jnp.split(xdbl, [dtr, dtr + m.d_state], axis=-1)
    dt = jax.nn.softplus(
        apply_w(dt_raw, params["dt_proj"], dt_)
        + params["dt_bias"].astype(dt_)).astype(jnp.float32)  # (B,di)
    a = -jnp.exp(params["A_log"])
    da = jnp.exp(dt[..., None] * a[None])            # (B,di,ds)
    dbx = (dt * xi.astype(jnp.float32))[..., None] \
        * b_ssm.astype(jnp.float32)[:, None, :]
    h = cache["ssm"] * da + dbx
    y = jnp.einsum("bds,bs->bd", h, c_ssm.astype(jnp.float32)).astype(dt_)
    y = y + xi * params["D"].astype(dt_)[None]
    y = y * jax.nn.silu(z[:, 0])
    out = apply_w(y, params["out_proj"], dt_)[:, None]
    return out, {"conv": conv_state.astype(cache["conv"].dtype), "ssm": h}


# ======================================================================
# xLSTM — mLSTM (chunkwise-parallel) and sLSTM (sequential scan)
# ======================================================================
def _mlstm_dims(cfg):
    di = int(cfg.xlstm.proj_factor_m * cfg.d_model)
    dh = di // cfg.n_heads
    return di, dh


def init_mlstm(key, cfg) -> dict:
    di, _ = _mlstm_dims(cfg)
    x = cfg.xlstm
    ks = jax.random.split(key, 8)
    return {
        "up_proj": dense_init(ks[0], (cfg.d_model, 2 * di)),
        "conv_w": dense_init(ks[1], (x.conv_kernel, di)),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "wq": dense_init(ks[2], (di, di)),
        "wk": dense_init(ks[3], (di, di)),
        "wv": dense_init(ks[4], (di, di)),
        "wi": dense_init(ks[5], (di, cfg.n_heads)),
        "wf": dense_init(ks[6], (di, cfg.n_heads)),
        "bi": jnp.zeros((cfg.n_heads,), jnp.float32),
        "bf": jnp.full((cfg.n_heads,), 3.0, jnp.float32),  # open f at init
        "out_norm": jnp.zeros((di,), jnp.float32),
        "down_proj": dense_init(ks[7], (di, cfg.d_model)),
    }


def mlstm_axes(cfg) -> dict:
    return {
        "up_proj": ("embed", "inner"), "conv_w": ("conv", "inner"),
        "conv_b": ("inner",), "wq": ("inner", "inner"),
        "wk": ("inner", "inner"), "wv": ("inner", "inner"),
        "wi": ("inner", "gates"), "wf": ("inner", "gates"),
        "bi": ("gates",), "bf": ("gates",), "out_norm": ("inner",),
        "down_proj": ("inner", "embed"),
    }


def _mlstm_gates(params, xc, b, s, h):
    li = apply_w(xc, params["wi"], xc.dtype).astype(jnp.float32) \
        + params["bi"]                                 # (B,S,H) log-i
    lf = jax.nn.log_sigmoid(
        apply_w(xc, params["wf"], xc.dtype).astype(jnp.float32)
        + params["bf"])                                # (B,S,H) log-f
    return li, lf


def mlstm_forward(params, x, cfg, spec, positions, return_cache=False):
    """Chunkwise-parallel mLSTM. x: (B,S,d) → (B,S,d)."""
    di, dh = _mlstm_dims(cfg)
    hn = cfg.n_heads
    b, s, _ = x.shape
    dt_ = x.dtype
    c = min(cfg.xlstm.chunk, s)
    assert s % c == 0
    nc = s // c

    xz = apply_w(x, params["up_proj"], dt_)
    xm, z = jnp.split(xz, 2, axis=-1)
    xc, conv_tail = _causal_conv(xm, wload(params["conv_w"], dt_),
                                 params["conv_b"].astype(dt_))
    xc = jax.nn.silu(xc)
    q = apply_w(xc, params["wq"], dt_).reshape(b, s, hn, dh)
    k = apply_w(xc, params["wk"], dt_).reshape(b, s, hn, dh) / np.sqrt(dh)
    v = apply_w(xm, params["wv"], dt_).reshape(b, s, hn, dh)
    li, lf = _mlstm_gates(params, xc, b, s, hn)

    # chunk views: (B, nc, c, ...) → scan over nc
    qc = jnp.moveaxis(q.reshape(b, nc, c, hn, dh), 1, 0)
    kc = jnp.moveaxis(k.reshape(b, nc, c, hn, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, c, hn, dh), 1, 0)
    lic = jnp.moveaxis(li.reshape(b, nc, c, hn), 1, 0)
    lfc = jnp.moveaxis(lf.reshape(b, nc, c, hn), 1, 0)

    @jax.checkpoint
    def chunk_body(carry, inp):
        cbar, nbar, mbar = carry       # (B,H,dh,dh), (B,H,dh), (B,H)
        q_j, k_j, v_j, li_j, lf_j = inp
        # gate math in fp32 end-to-end (also: XLA:CPU lacks some
        # bf16×bf16→f32 dot shapes these einsums would hit)
        q_j = q_j.astype(jnp.float32)
        k_j = k_j.astype(jnp.float32)
        v_j = v_j.astype(jnp.float32)
        # cumulative log-f within chunk, inclusive: F_t = Σ_{s≤t} lf_s
        f_cum = jnp.cumsum(lf_j, axis=1)                     # (B,c,H)
        # intra-chunk scores: a[t,s] = F_t − F_s + li_s (s ≤ t)
        a_mat = f_cum[:, :, None, :] - f_cum[:, None, :, :] \
            + li_j[:, None, :, :]                            # (B,c,c,H)
        tri = jnp.tril(jnp.ones((c, c), bool))
        a_mat = jnp.where(tri[None, :, :, None], a_mat, -jnp.inf)
        m_intra = jnp.max(a_mat, axis=2)                     # (B,c,H)
        # inter-chunk (state) branch log-weight: F_t + m̄
        m_state = f_cum + mbar[:, None, :]                   # (B,c,H)
        m_tot = jnp.maximum(m_intra, m_state)
        m_tot = jnp.maximum(m_tot, -30.0)  # keeps exp(-m) sane when gates≈0
        d_mat = jnp.exp(a_mat - m_tot[:, :, None, :])        # (B,c,c,H)
        state_w = jnp.exp(m_state - m_tot)                   # (B,c,H)

        s_mat = jnp.einsum("bthd,bshd->btsh", q_j, k_j)
        cw = s_mat * d_mat                                   # (B,c,c,H)
        num_intra = jnp.einsum("btsh,bshd->bthd", cw, v_j)
        num_state = jnp.einsum("bthd,bhde->bthe", q_j, cbar) \
            * state_w[..., None]
        den_intra = jnp.sum(cw, axis=2)                      # (B,c,H)
        den_state = jnp.einsum("bthd,bhd->bth", q_j, nbar) * state_w
        den = jnp.maximum(jnp.abs(den_intra + den_state),
                          jnp.exp(-m_tot)) + 1e-6
        h_out = (num_intra + num_state) / den[..., None]     # (B,c,H,dh)

        # ---- state update to end of chunk ----
        f_tot = f_cum[:, -1, :]                              # (B,H)
        bmat = f_tot[:, None, :] - f_cum + li_j              # (B,c,H)
        m_new = jnp.maximum(f_tot + mbar, jnp.max(bmat, axis=1))
        m_new = jnp.maximum(m_new, -30.0)
        w_s = jnp.exp(bmat - m_new[:, None, :])              # (B,c,H)
        carry_scale = jnp.exp(f_tot + mbar - m_new)          # (B,H)
        kv = jnp.einsum("bshd,bshe->bhde", k_j * w_s[..., None], v_j)
        c_new = cbar * carry_scale[..., None, None] + kv
        n_new = nbar * carry_scale[..., None] \
            + jnp.sum(k_j * w_s[..., None], axis=1)
        return (c_new, n_new, m_new), h_out.astype(dt_)

    c0 = jnp.zeros((b, hn, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, hn, dh), jnp.float32)
    m0 = jnp.full((b, hn), -30.0, jnp.float32)
    (c_f, n_f, m_f), hs = jax.lax.scan(chunk_body, (c0, n0, m0),
                                       (qc, kc, vc, lic, lfc))
    hseq = jnp.moveaxis(hs, 0, 1).reshape(b, s, di)
    hseq = rms_norm(hseq, params["out_norm"], cfg.norm_eps)
    out = hseq * jax.nn.silu(z)
    y = apply_w(out, params["down_proj"], dt_)
    if not return_cache:
        return y
    return y, {"conv": conv_tail, "C": c_f, "n": n_f, "m": m_f}


def init_mlstm_cache(cfg, spec, batch: int, max_len: int, dtype) -> dict:
    di, dh = _mlstm_dims(cfg)
    x = cfg.xlstm
    return {
        "conv": jnp.zeros((batch, x.conv_kernel - 1, di), dtype),
        "C": jnp.zeros((batch, cfg.n_heads, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, cfg.n_heads, dh), jnp.float32),
        "m": jnp.full((batch, cfg.n_heads), -30.0, jnp.float32),
    }


def mlstm_decode(params, x, cache, pos, cfg, spec, layer_idx=None):
    if layer_idx is not None:  # layer-stacked cache (scanned decode)
        local = read_layer_cache(cache, layer_idx)
        out, new_local = mlstm_decode(params, x, local, pos, cfg, spec)
        return out, write_layer_cache(cache, new_local, layer_idx)
    di, dh = _mlstm_dims(cfg)
    hn = cfg.n_heads
    b = x.shape[0]
    dt_ = x.dtype

    xz = apply_w(x, params["up_proj"], dt_)
    xm, z = jnp.split(xz, 2, axis=-1)
    xc, conv_state = _causal_conv(
        xm, wload(params["conv_w"], dt_), params["conv_b"].astype(dt_),
        state=cache["conv"])
    xc = jax.nn.silu(xc)[:, 0]
    xm = xm[:, 0]
    q = apply_w(xc, params["wq"], dt_).reshape(b, hn, dh)
    k = apply_w(xc, params["wk"], dt_).reshape(b, hn, dh) / np.sqrt(dh)
    v = apply_w(xm, params["wv"], dt_).reshape(b, hn, dh)
    li = apply_w(xc, params["wi"], dt_).astype(jnp.float32) + params["bi"]
    lf = jax.nn.log_sigmoid(
        apply_w(xc, params["wf"], dt_).astype(jnp.float32) + params["bf"])

    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    m_new = jnp.maximum(lf + cache["m"], li)
    m_new = jnp.maximum(m_new, -30.0)
    fp = jnp.exp(lf + cache["m"] - m_new)[..., None]          # (B,H,1)
    ip = jnp.exp(li - m_new)[..., None]
    c_new = cache["C"] * fp[..., None] \
        + ip[..., None] * (k[..., :, None] * v[..., None, :])
    n_new = cache["n"] * fp + ip * k
    num = jnp.einsum("bhd,bhde->bhe", q, c_new)
    den = jnp.maximum(
        jnp.abs(jnp.einsum("bhd,bhd->bh", q, n_new)),
        jnp.exp(-m_new)) + 1e-6
    hvec = (num / den[..., None]).reshape(b, di).astype(dt_)
    hvec = rms_norm(hvec, params["out_norm"], cfg.norm_eps)
    out = apply_w(hvec * jax.nn.silu(z[:, 0]), params["down_proj"], dt_)
    return out[:, None], {
        "conv": conv_state.astype(cache["conv"].dtype),
        "C": c_new, "n": n_new, "m": m_new}


# ----------------------------------------------------------------------
# sLSTM
# ----------------------------------------------------------------------
def _slstm_dims(cfg):
    di = cfg.d_model                      # no up-projection in the core
    dh = di // cfg.n_heads
    ff = int(cfg.xlstm.proj_factor_s * cfg.d_model)
    ff = (ff + 63) // 64 * 64
    return di, dh, ff


def init_slstm(key, cfg) -> dict:
    di, dh, ff = _slstm_dims(cfg)
    ks = jax.random.split(key, 4)
    r = jax.random.normal(ks[1], (4, cfg.n_heads, dh, dh),
                          jnp.float32) / np.sqrt(dh)
    b = jnp.zeros((4 * di,), jnp.float32)
    b = b.at[di:2 * di].set(3.0)          # forget-gate bias (order i,f,z,o)
    return {
        "w": dense_init(ks[0], (cfg.d_model, 4 * di)),
        "r": r,
        "b": b,
        "out_norm": jnp.zeros((di,), jnp.float32),
        "up_proj": dense_init(ks[2], (di, 2 * ff)),
        "down_proj": dense_init(ks[3], (ff, cfg.d_model)),
    }


def slstm_axes(cfg) -> dict:
    return {
        # r stays replicated: sharding the (4, H, dh, dh) recurrent
        # matrices over "model" costs a psum per TIME STEP inside the
        # sequential scan (measured: xlstm train_4k went collective-bound
        # purely from this) — the matrices are tiny, replicate them
        "w": ("embed", "inner"), "r": ("stack", None, None, None),
        "b": ("inner",), "out_norm": ("inner",),
        "up_proj": ("inner", "mlp"), "down_proj": ("mlp", "embed"),
    }


def _slstm_cell(params, wx_t, state, cfg):
    """One sLSTM step. wx_t: (B, 4*di) precomputed input contribution."""
    di, dh, _ = _slstm_dims(cfg)
    hn = cfg.n_heads
    c, n, hprev, m = state
    hh = hprev.reshape(-1, hn, dh)
    rec = jnp.einsum("bhd,ghde->bghe", hh, params["r"])   # (B,4,H,dh)
    pre = wx_t.reshape(-1, 4, di) + rec.reshape(-1, 4, di) \
        + params["b"].reshape(4, di)[None]
    it, ft, zt, ot = pre[:, 0], pre[:, 1], pre[:, 2], pre[:, 3]
    lf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(lf + m, it)
    i_s = jnp.exp(it - m_new)
    f_s = jnp.exp(lf + m - m_new)
    c_new = f_s * c + i_s * jnp.tanh(zt)
    n_new = f_s * n + i_s
    h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, h_new, m_new), h_new


def slstm_forward(params, x, cfg, spec, positions, return_cache=False):
    di, dh, ff = _slstm_dims(cfg)
    b, s, _ = x.shape
    dt_ = x.dtype
    wx = apply_w(x, params["w"], dt_).astype(jnp.float32)   # (B,S,4di)

    def step(state, wx_t):
        return _slstm_cell(params, wx_t, state, cfg)

    z = jnp.zeros((b, di), jnp.float32)
    st0 = (z, z, z, jnp.full((b, di), -30.0, jnp.float32))
    (c_f, n_f, h_f, m_f), hs = jax.lax.scan(step, st0,
                                            jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).astype(dt_)                  # (B,S,di)
    h = rms_norm(h, params["out_norm"], cfg.norm_eps)
    u, g = jnp.split(apply_w(h, params["up_proj"], dt_), 2, axis=-1)
    y = apply_w(u * jax.nn.silu(g), params["down_proj"], dt_)
    if not return_cache:
        return y
    return y, {"c": c_f, "n": n_f, "h": h_f, "m": m_f}


def init_slstm_cache(cfg, spec, batch: int, max_len: int, dtype) -> dict:
    di, _, _ = _slstm_dims(cfg)
    # one array per leaf: the serving engine donates the cache, and a
    # buffer shared by two leaves cannot be donated twice
    return {"c": jnp.zeros((batch, di), jnp.float32),
            "n": jnp.zeros((batch, di), jnp.float32),
            "h": jnp.zeros((batch, di), jnp.float32),
            "m": jnp.full((batch, di), -30.0, jnp.float32)}


def slstm_decode(params, x, cache, pos, cfg, spec, layer_idx=None):
    if layer_idx is not None:  # layer-stacked cache (scanned decode)
        local = read_layer_cache(cache, layer_idx)
        out, new_local = slstm_decode(params, x, local, pos, cfg, spec)
        return out, write_layer_cache(cache, new_local, layer_idx)
    dt_ = x.dtype
    wx = apply_w(x[:, 0], params["w"], dt_).astype(jnp.float32)
    st = (cache["c"], cache["n"], cache["h"], cache["m"])
    (c, n, h, m), _ = _slstm_cell(params, wx, st, cfg)
    hn = rms_norm(h.astype(dt_), params["out_norm"], cfg.norm_eps)
    u, g = jnp.split(apply_w(hn, params["up_proj"], dt_), 2, axis=-1)
    out = apply_w(u * jax.nn.silu(g), params["down_proj"], dt_)[:, None]
    return out, {"c": c, "n": n, "h": h, "m": m}
