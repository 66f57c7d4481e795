"""attn_ms, checked on event lists whose answers are known and on the
scopes of a train step compiled here.

Run from the root of the checkout:

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests
"""
from __future__ import annotations

import pytest

import tiny  # noqa: F401  (puts the benchmark's modules on the path)
import tracing

D0, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE
ATTN = "jit(train_step)/transpose(jvp(jvp()))/checkpoint/blockwise_attention"


def ev(plane, line, name, start, end, *scope):
    return (plane, line, name, float(start), float(end), *scope)


def attn_ms(trace):
    from harness import HERE
    from refs import load_module
    return load_module(HERE / "metrics" / "attn_ms.py",
                       "metric_attn_ms").read({"trace": trace})


def attention_steps(scoped=True):
    """Two train steps of 4000 ns: in each, a backward loop of 1500 ns
    whose body runs an op of 1000 ns, a forward op of 500 ns, an op of
    another scope and, in the first, a copy with no scope of its own;
    an attention op of another program."""
    sc = (lambda s: (s,)) if scoped else (lambda s: ())
    out = [ev(HOST, "python3", "bench.window", 0, 20000)]
    for t in (1000, 6000):
        out += [
            ev(D0, MODS, "jit_train_step(3)", t, t + 4000),
            ev(D0, OPS, "while.9", t, t + 1500, *sc(ATTN + "/while")),
            ev(D0, OPS, "fusion.4", t + 200, t + 1200,
               *sc(ATTN + "/while/body/dot_general")),
            ev(D0, OPS, "fusion.5", t + 2000, t + 2500,
               *sc("jit(train_step)/jvp(blockwise_attention)/exp")),
            ev(D0, OPS, "fusion.6", t + 2500, t + 3900,
               *sc("jit(train_step)/jvp()/dot_general")),
        ]
    out += [ev(D0, OPS, "copy.1", 1600, 1700),
            ev(D0, MODS, "jit_prefill(1)", 12000, 13000),
            ev(D0, OPS, "fusion.1", 12000, 13000,
               *sc("jit(prefill)/blockwise_attention/dot_general"))]
    return out


def test_attn_ms_is_scoped_time_per_train_step():
    # (1500 + 500) ns a step: the loop's body and the copy inside it
    # counted once, the other scope and the other program not at all
    assert attn_ms(tracing.Trace(attention_steps())) == \
        pytest.approx(2000e-6)


def test_attn_ms_without_the_scope_is_none():
    """A program with no such scope, or a trace whose ops were given no
    scope, reads nothing."""
    assert attn_ms(tracing.Trace(attention_steps(scoped=False))) is None
    no_attention = [e for e in attention_steps()
                    if "blockwise_attention" not in "".join(e[5:])]
    assert attn_ms(tracing.Trace(no_attention)) is None


def test_attn_ms_reads_the_scopes_a_compiled_train_step_carries():
    """The scopes of a real program: a checkpointed attention layer's
    train step, compiled here, its instructions' op_names read back by
    ``add_hlo``; each instruction runs once for 100 ns."""
    import jax
    import jax.numpy as jnp
    from repro.models.attention import blockwise_attention

    pos = jnp.arange(16)

    def train_step(q, k, v):
        attend = jax.checkpoint(lambda q: blockwise_attention(
            q, k, v, pos, pos, q_chunk=8, kv_chunk=8))
        return jax.grad(lambda q: jnp.sum(jnp.tanh(attend(q))))(q)

    x = jnp.ones((1, 16, 2, 8), jnp.float32)
    text = jax.jit(train_step).lower(x, x, x).compile().as_text()
    names = list(tracing.hlo_scopes(text.split("\nENTRY", 1)[1]))
    scopes = tracing.hlo_scopes(text)
    attn = [n for n in names if "blockwise_attention" in scopes[n]]
    assert attn and len(attn) < len(names)
    evs = [ev(HOST, "python3", "bench.window", 0, 1e6),
           ev(D0, MODS, "jit_train_step(1)", 0, 100 * len(names))]
    evs += [ev(D0, OPS, n, 100 * i, 100 * i + 100)
            for i, n in enumerate(names)]
    t = tracing.Trace(evs)
    assert attn_ms(t) is None
    t.add_hlo([text])
    assert attn_ms(t) == pytest.approx(100 * len(attn) * 1e-6)
