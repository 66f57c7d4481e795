"""A mixture-of-experts cell: the reference's loss takes the router's
balance term, ``aux_gap`` reads the term on its own, and a file with
``moe`` is held to the load-time rules.

The cell is Phi-3-mini's block with its MLP replaced by DeepSeekMoE's
routed experts, cut by ``tiny.py`` (4 experts, 2 a token, 1 shared), two
layers scanned, so that the k-means tasks split the expert leaves
``(L, E, d, f)`` per layer and expert (``stack_ndim`` 2). Its capacity
factor is ``n_experts / top_k``: no token is dropped, as the reference
(``moe-tiny.ref.py``, beside this file) drops none. The limits are the
phi3 cell's, with ``aux_gap`` at 1e-3: a sound float32 run reads about
5e-8, the router fault about 0.13.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests
"""
from __future__ import annotations

import json
import time

import pytest

import tiny

import harness
import lcjob
import refs

SEED = 2 ** 33 + 17
# DeepSeekMoE's routed and shared experts, before tiny.py cuts them
MOE = {"n_experts": 64, "top_k": 6, "d_expert": 1408, "n_shared": 2,
       "capacity_factor": 1.0, "router_aux_weight": 0.01}
TASKS = [{"scheme": "kmeans", "per_leaf": True, "k": 16, "iters": 10,
          "pattern": "stages/.*/(wq|wk|wv|wo|sw_gate|sw_up|sw_down)$",
          "stack_ndim": 1},
         {"scheme": "kmeans", "per_leaf": True, "k": 16, "iters": 10,
          "pattern": "stages/.*/ffn/(w_gate|w_up|w_down)$", "stack_ndim": 2}]
AUX_GAP = 1e-3


def moe_file() -> dict:
    c = json.loads((tiny.BENCH / "configs" / "phi3-mini-3.8b.json")
                   .read_text())
    c.update(name="moe-tiny", pattern=[["attn", "moe", 2048]],
             pattern_reps=2, moe=dict(MOE),
             reference=str(tiny.HERE / "moe-tiny.ref.py"))
    return c


def moe_cell(config: dict | None = None, **limits) -> dict:
    lim = json.loads((tiny.BENCH / "limits" / "phi3-mini-3.8b.lc-quant4.json")
                     .read_text())
    lim.update(aux_gap=AUX_GAP, **limits)
    out = tiny.tiny_cell("moe-tiny.lc-quant4", config or moe_file(),
                         "lc-quant4-b1s2048", lim, tasks=TASKS)
    c = out["config_file"]
    c["moe"]["capacity_factor"] = c["moe"]["n_experts"] / c["moe"]["top_k"]
    c["dtype"] = "float32"
    return out


def moe_run(fault=None):
    return lcjob.run(moe_cell(), seed=SEED, seconds=1.0, trace=False,
                     t_start=time.perf_counter(), device=tiny.CPU,
                     fault=fault)


def test_moe_sound_run_is_correct():
    res = moe_run()
    assert res["correct"], res["checks"]
    assert res["window_compiles"] == 0
    assert res["checks"]["aux_gap"]["value"] < 1e-5      # round-off


def test_moe_balance_term_left_out_is_not_correct():
    """The program's loss without ``router_aux_weight * aux``: the router
    is as sound as before, so ``aux_gap`` stays at round-off, and the loss
    misses the term's share (about 0.7 % here)."""
    res = moe_run("aux_dropped")
    assert not res["correct"], res["checks"]
    loss = res["checks"]["loss_gap"]
    assert loss["value"] > 2 * loss["limit"], res["checks"]


def test_moe_router_choice_altered_reads_aux_gap_above_its_limit():
    """The router takes its k least likely experts."""
    res = moe_run("router_flipped")
    assert not res["correct"], res["checks"]
    aux = res["checks"]["aux_gap"]
    assert aux["value"] > 10 * aux["limit"], res["checks"]


def test_zero_weight_reference_reads_the_unweighted_term():
    """At weight 0 the term adds nothing to the loss, and ``aux_gap``
    still reads it: the first step's term is the one read at 0.01."""
    cell = moe_cell()
    ref = lcjob.reference_readings(cell, SEED, refs.MatMul())
    harness.free_device()
    c = cell["config_file"]
    c["moe"]["router_aux_weight"] = 0.0
    c["assumed"] = dict(c["assumed"], router_aux_weight="test")
    zero = lcjob.reference_readings(cell, SEED, refs.MatMul())
    assert zero["aux"][0] == pytest.approx(ref["aux"][0], rel=1e-5)
    assert zero["loss"][0] < ref["loss"][0]


def test_switch_balance_from_its_definition():
    import jax.numpy as jnp
    e, k = 4, 2
    # uniform probabilities, each expert chosen equally often: k
    probs = jnp.full((8, e), 1.0 / e)
    chosen = jnp.array([[0, 1], [2, 3]] * 4)
    assert float(refs.switch_balance(probs, chosen, e)) == pytest.approx(k)
    # every token on experts 0 and 1, which hold all the probability: E
    probs = jnp.array([[0.5, 0.5, 0.0, 0.0]] * 8)
    chosen = jnp.array([[0, 1]] * 8)
    assert float(refs.switch_balance(probs, chosen, e)) == pytest.approx(e)


# ----------------------------------------------------------------------
# load-time rules
# ----------------------------------------------------------------------
def _weight_left_out(c):
    del c["moe"]["router_aux_weight"]


def _weight_zero(c):
    c["moe"]["router_aux_weight"] = 0.0


@pytest.mark.parametrize("edit", [_weight_left_out, _weight_zero],
                         ids=["left_out", "zero_unexplained"])
def test_moe_file_states_its_balance_weight(edit):
    c = moe_file()
    edit(c)
    with pytest.raises(ValueError, match="router_aux_weight"):
        harness.model_config(c)


def test_zero_balance_weight_with_a_reason_loads():
    c = moe_file()
    c["moe"]["router_aux_weight"] = 0.0
    c["assumed"] = dict(c["assumed"], router_aux_weight="as published")
    assert harness.model_config(c).moe.router_aux_weight == 0.0


def test_moe_reference_without_aux_loss_raises():
    c = moe_file()
    c["reference"] = "phi3-mini-3.8b.ref.py"
    with pytest.raises(ValueError, match="aux_loss"):
        lcjob.model_config(c)


def test_moe_limits_without_aux_gap_raise():
    cell = moe_cell()
    del cell["limits"]["aux_gap"]
    with pytest.raises(ValueError,
                       match=r"limits/moe-tiny\.lc-quant4\.json .*aux_gap"):
        lcjob.cell_config(cell)
