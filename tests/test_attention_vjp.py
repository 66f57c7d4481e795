"""The backward of ``blockwise_attention``: its gradients against
``jax.grad`` of the naive oracle, alone and under ``jax.checkpoint`` in a
``lax.scan`` as the layer stack calls it; in bf16 against a float32
reference at ``highest`` precision, beside the scan's own autodiff; and a
forward that is the plain online-softmax scan's, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import NEG_INF, _mask, blockwise_attention
from test_models import ATTN_GRID as GRID
from test_models import KEY, _naive_attn


def _scan_attention(q, k, v, q_positions, k_positions, *, window=0,
                    q_chunk=1024, kv_chunk=1024, scale=None):
    """The online-softmax scan with no VJP of its own, differentiated by
    ``lax.scan``'s autodiff: ``blockwise_attention`` as it was before it
    had a backward."""
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qc, kc = min(q_chunk, sq), min(kv_chunk, sk)
    nq, nk = sq // qc, sk // kc
    qr = jnp.moveaxis(q.reshape(b, nq, qc, kv, g, d), 1, 0)
    kr = jnp.moveaxis(k.reshape(b, nk, kc, kv, d), 1, 0)
    vr = jnp.moveaxis(v.reshape(b, nk, kc, kv, dv), 1, 0)
    qp = q_positions.reshape(nq, qc)
    kp = k_positions.reshape(nk, kc)

    def q_chunk_body(_, qi):
        q_i, qp_i = qi
        m0 = jnp.full((b, kv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kv, g, qc, dv), jnp.float32)

        def kv_chunk_body(carry, kj):
            m, l, acc = carry
            k_j, v_j, kp_j = kj
            s = jnp.einsum("bqkgd,bckd->bkgqc", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _mask(qp_i, kp_j, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(v_j.dtype), v_j,
                            preferred_element_type=jnp.float32)
            return (m_new, l_new, acc * alpha[..., None] + pv), None

        (m, l, acc), _ = jax.lax.scan(
            kv_chunk_body, (m0, l0, a0), (kr, vr, kp))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_chunk_body, None, (qr, qp))
    out = jnp.moveaxis(jnp.moveaxis(outs, 0, 1), 4, 2)
    return out.reshape(b, sq, h, dv)


def _naive_mla(q, k, v, scale):
    """Causal softmax attention with its own scale and a v head size of
    its own (MLA), one head per kv head."""
    s = jnp.einsum("bqhd,bchd->bhqc", q, k) * scale
    i = jnp.arange(q.shape[1])
    s = jnp.where((i[None, :] <= i[:, None])[None, None], s, -1e30)
    return jnp.einsum("bhqc,bchd->bqhd", jax.nn.softmax(s, -1), v)


def _inputs(b, s, h, kvh, d, dv=None, dtype=jnp.float32):
    kq, kk, kv_, ko = jax.random.split(KEY, 4)
    dv = dv or d
    return (jax.random.normal(kq, (b, s, h, d)).astype(dtype),
            jax.random.normal(kk, (b, s, kvh, d)).astype(dtype),
            jax.random.normal(kv_, (b, s, kvh, dv)).astype(dtype),
            jax.random.normal(ko, (b, s, h, dv)).astype(dtype))


def _assert_grads_close(got, want, atol=1e-5):
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("b,s,h,kvh,d,w,qc,kc", GRID)
def test_blockwise_attention_grad_matches_naive(b, s, h, kvh, d, w, qc, kc):
    q, k, v, ct = _inputs(b, s, h, kvh, d)
    pos = jnp.arange(s)

    def f(q, k, v):
        return jnp.sum(ct * blockwise_attention(
            q, k, v, pos, pos, window=w, q_chunk=qc, kv_chunk=kc))

    def ref(q, k, v):
        return jnp.sum(ct * _naive_attn(q, k, v, w))

    _assert_grads_close(jax.grad(f, (0, 1, 2))(q, k, v),
                        jax.grad(ref, (0, 1, 2))(q, k, v))


@pytest.mark.parametrize("b,s,h,kvh,d,w,qc,kc", GRID)
def test_blockwise_attention_grad_under_checkpoint_in_scan(
        b, s, h, kvh, d, w, qc, kc):
    """Two layers in a ``lax.scan`` over a checkpointed body, as
    ``models/transformer.py`` stacks them: each scales the running q by
    its own weight and attends."""
    q, k, v, ct = _inputs(b, s, h, kvh, d)
    pos = jnp.arange(s)
    ws = jnp.array([0.7, 1.3])

    def stack(attend):
        def loss(q, k, v, ws):
            def body(x, w_l):
                return attend(x * w_l, k, v), None
            x, _ = jax.lax.scan(jax.checkpoint(body), q, ws)
            return jnp.sum(ct * x)
        return loss

    f = stack(lambda q, k, v: blockwise_attention(
        q, k, v, pos, pos, window=w, q_chunk=qc, kv_chunk=kc))
    ref = stack(lambda q, k, v: _naive_attn(q, k, v, w))
    got = jax.grad(f, (0, 1, 2, 3))(q, k, v, ws)
    want = jax.grad(ref, (0, 1, 2, 3))(q, k, v, ws)
    _assert_grads_close(got[:3], want[:3])
    np.testing.assert_allclose(np.asarray(got[3]), np.asarray(want[3]),
                               rtol=1e-5)


def test_blockwise_attention_mla_grad():
    """v head size ≠ qk head size, and an explicit scale (MLA)."""
    q, k, v, ct = _inputs(2, 32, 4, 4, 24, dv=8)
    pos = jnp.arange(32)
    scale = 1.0 / np.sqrt(20.0)

    def f(q, k, v):
        return jnp.sum(ct * blockwise_attention(
            q, k, v, pos, pos, q_chunk=8, kv_chunk=8, scale=scale))

    def ref(q, k, v):
        return jnp.sum(ct * _naive_mla(q, k, v, scale))

    got = jax.grad(f, (0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    _assert_grads_close(got, jax.grad(ref, (0, 1, 2))(q, k, v))


def _rel_err(a, ref):
    a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def test_blockwise_attention_bf16_grad_error():
    """bf16 inputs, a window and GQA: the gradient's error against a
    float32 reference at ``highest`` precision is no larger than that of
    the scan's own autodiff, within a tenth."""
    b, s, h, kvh, d, w, c = 1, 256, 8, 4, 64, 200, 64
    q, k, v, ct = _inputs(b, s, h, kvh, d, dtype=jnp.bfloat16)
    pos = jnp.arange(s)

    def loss(attend, *qkv):
        return jnp.sum(ct.astype(jnp.float32)
                       * attend(*qkv).astype(jnp.float32))

    def custom(q, k, v):
        return blockwise_attention(q, k, v, pos, pos, window=w,
                                   q_chunk=c, kv_chunk=c)

    def scan(q, k, v):
        return _scan_attention(q, k, v, pos, pos, window=w,
                               q_chunk=c, kv_chunk=c)

    got = jax.grad(lambda *a: loss(custom, *a), (0, 1, 2))(q, k, v)
    before = jax.grad(lambda *a: loss(scan, *a), (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        want = jax.grad(lambda *a: loss(lambda q, k, v: _naive_attn(
            q, k, v, w), *a), (0, 1, 2))(*f32)
    for name, g, g0, r in zip("qkv", got, before, want):
        assert g.dtype == jnp.bfloat16, name
        err, err0 = _rel_err(g, r), _rel_err(g0, r)
        assert err <= 1.1 * err0 + 1e-4, (name, err, err0)
        assert err < 2e-2, (name, err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kvh,d,w,qc,kc", GRID)
def test_blockwise_attention_forward_is_the_scan_bit_for_bit(
        b, s, h, kvh, d, w, qc, kc, dtype):
    """The output, called plainly and as the primal of ``jax.vjp``, is
    the plain scan's to the last bit."""
    q, k, v, _ = _inputs(b, s, h, kvh, d, dtype=dtype)
    pos = jnp.arange(s)
    kw = dict(window=w, q_chunk=qc, kv_chunk=kc)
    want = np.asarray(jax.jit(
        lambda q, k, v: _scan_attention(q, k, v, pos, pos, **kw))(q, k, v))
    plain = jax.jit(
        lambda q, k, v: blockwise_attention(q, k, v, pos, pos, **kw))
    primal = jax.jit(lambda q, k, v: jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, pos, pos, **kw),
        q, k, v)[0])
    np.testing.assert_array_equal(np.asarray(plain(q, k, v)), want)
    np.testing.assert_array_equal(np.asarray(primal(q, k, v)), want)
