"""Configuration files and compression tasks as the harness reads them.

Every architecture the program lists is written as a configuration file
and must come back as the same ``ModelConfig``; the k-means tasks'
``stack_ndim`` must give the program and the reference the same items.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests
"""
from __future__ import annotations

import dataclasses
import json

import jax
import numpy as np
import pytest

import tiny

import harness
import lcjob
from repro.configs import ARCHS, get_config, reduced_config
from repro.configs.base import LayerSpec, MLACfg, ModelConfig
from repro.core import AsStacked, AsVector, LCAlgorithm
from repro.core.tasks import flatten_params
from repro.models.transformer import init_params

SPECS = ("pattern", "lead", "tail")


def file_dict(cfg: ModelConfig) -> dict:
    """``cfg`` written as a configuration file: every field, layer specs
    as ``[mixer, ffn, window]``, nested blocks as objects, through JSON."""
    out = {}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if f.name in SPECS:
            v = [[s.mixer, s.ffn, s.window] for s in v]
        elif dataclasses.is_dataclass(v):
            v = dataclasses.asdict(v)
        out[f.name] = v
    return json.loads(json.dumps(out))


@pytest.mark.parametrize("arch", ARCHS)
def test_config_file_round_trips(arch):
    cfg = get_config(arch)
    assert harness.model_config(file_dict(cfg)) == cfg


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_file_builds_and_inits(arch):
    cfg = reduced_config(get_config(arch))
    got = harness.model_config(file_dict(cfg))
    assert got == cfg
    shapes = jax.eval_shape(lambda k: init_params(k, got),
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_leaves(shapes)


@pytest.mark.parametrize("key,where", [("n_layers", None),
                                       ("top_kk", "moe"),
                                       ("kv_rank", "mla")])
def test_key_that_is_no_field_raises(key, where):
    c = file_dict(get_config("deepseek-moe-16b"))
    c["mla"] = dataclasses.asdict(MLACfg())
    (c[where] if where else c)[key] = 1
    with pytest.raises((ValueError, TypeError), match=key):
        harness.model_config(c)


def test_null_q_lora_rank_reaches_mla_as_none():
    """DeepSeek-V2-Lite's attention has no query LoRA: its file gives
    ``"q_lora_rank": null`` beside MoE experts and a leading dense layer."""
    c = file_dict(get_config("deepseek-moe-16b"))
    c["mla"] = {"q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_dim": 128,
                "qk_rope_dim": 64, "v_head_dim": 128}
    c["pattern"] = [["mla", "moe", 0]]
    c["lead"] = [["mla", "dense", 0]]
    c = tiny.tiny_config(c)
    cfg = harness.model_config(c)
    assert cfg.mla == MLACfg(q_lora_rank=None, kv_lora_rank=16,
                             qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
    assert cfg.moe.n_experts == 4 and cfg.lead == (LayerSpec("mla", "dense"),)
    assert not cfg.subquadratic


def test_phi3_file_builds_the_same_config():
    c = json.loads((tiny.BENCH / "configs" / "phi3-mini-3.8b.json").read_text())
    assert harness.model_config(c) == ModelConfig(
        name="phi3-mini-3.8b", d_model=3072, n_heads=32, n_kv_heads=32,
        head_dim=96, d_ff=8192, vocab_size=32064,
        pattern=(LayerSpec("attn", "dense", window=2048),), pattern_reps=1,
        rope_theta=10000.0, norm_eps=1e-05, tie_embeddings=False,
        dtype="bfloat16", remat=True, attn_chunk_q=1024, attn_chunk_kv=1024,
        subquadratic=False)


@pytest.mark.parametrize("arch,expected", [
    ("phi3-mini-3.8b", False), ("minicpm3-4b", False),
    ("jamba-v0.1-52b", False), ("xlstm-125m", True)])
def test_subquadratic_derived_when_left_out(arch, expected):
    c = file_dict(get_config(arch))
    del c["subquadratic"]
    assert harness.model_config(c).subquadratic is expected


def test_embeddings_input_refused_at_load():
    c = file_dict(reduced_config(get_config("internvl2-1b")))
    with pytest.raises(ValueError, match="input_mode"):
        lcjob.model_config(c)


# ----------------------------------------------------------------------
# tasks and their items
# ----------------------------------------------------------------------
PHI3_TRAFFIC = json.loads(
    (tiny.BENCH / "traffic" / "lc-quant4-b1s2048.json").read_text())


def kmeans_task(pattern, **kw):
    return dict({"scheme": "kmeans", "pattern": pattern, "per_leaf": True,
                 "k": 16, "iters": 10}, **kw)


def test_phi3_tasks_as_before():
    cfg = harness.model_config(tiny.tiny_config("phi3-mini-3.8b"))
    tasks = lcjob.program_tasks(PHI3_TRAFFIC, cfg)
    names = sorted(f"stages/s0/pos0/{m}" for m in (
        "ffn/w_down", "ffn/w_gate", "ffn/w_up", "mixer/wk", "mixer/wo",
        "mixer/wq", "mixer/wv"))
    assert [t.name for t in tasks] == names
    assert all(type(t.view) is AsVector for t in tasks)
    shapes = flatten_params(jax.eval_shape(lambda k: init_params(k, cfg),
                                           jax.random.PRNGKey(0)))
    (task,) = PHI3_TRAFFIC["tasks"]
    assert lcjob.resolve_tasks(PHI3_TRAFFIC, shapes) == [
        dict(task, name=p, paths=[p]) for p in names]


ITEM_CASES = {
    # per-(layer, expert) codebooks on the scanned experts (2, 4, d, f)
    "moe-experts": ("deepseek-moe-16b", "s1/.*/ffn/(w_gate|w_up|w_down)$", 2,
                    8, [0, 0], [1, 3]),
    # per-layer codebooks on scanned latent-attention projections (2, d, f)
    "mla-layers": ("minicpm3-4b", "mixer/(wdq|wuq|wdkv|wukv|wo)$", 1,
                   2, [0], [1]),
}


@pytest.mark.parametrize("case", ITEM_CASES)
def test_program_and_reference_agree_on_items(case):
    arch, pattern, n, count, first, last = ITEM_CASES[case]
    cfg = harness.model_config(file_dict(reduced_config(get_config(arch))))
    tr = {"kind": "lc", "tasks": [kmeans_task(pattern, stack_ndim=n)],
          "ref_kmeans_iters": 30}
    params = init_params(jax.random.PRNGKey(3), cfg)
    lc = LCAlgorithm(lcjob.program_tasks(tr, cfg), [1e-3])
    state = lc.init(params)
    flat = {p: np.asarray(v, np.float32)
            for p, v in flatten_params(params).items()}
    ref_tasks = {t["name"]: t for t in lcjob.resolve_tasks(tr, flat)}
    assert [t.name for t in lc.tasks] == sorted(ref_tasks)
    for t in lc.tasks:
        assert type(t.view) is AsStacked and t.view.stack_ndim == n
        got = t.view.to_items(t.compressible(params))
        ref = lcjob.items(ref_tasks[t.name], [flat[t.paths[0]]])
        assert got.shape[0] == len(ref) == count
        np.testing.assert_array_equal(np.asarray(got), np.stack(ref))

    x = lcjob.to_host(lcjob._shift(params, state))
    new = lc.c_step(params, state)
    out = lcjob.cstep_readings(tr, x, lcjob.to_host(lcjob.lc_deltas(new)))
    labels = [r[0] for r in out["cstep_items"]]
    assert len(labels) == count * len(lc.tasks)
    tag = lambda ix: "[" + ",".join(map(str, ix)) + "]"
    assert labels[0] == lc.tasks[0].name + tag(first)
    assert labels[count - 1] == lc.tasks[0].name + tag(last)
    assert all(r[2] == 0 for r in out["cstep_items"])
    assert out["cstep_excess"] == 0
    assert np.isfinite(out["cstep_gap"])


@pytest.mark.parametrize("task", [
    {"scheme": "kmeans", "pattern": "w_gate$", "k": 16, "iters": 10},
    kmeans_task("ffn/w_gate$", stack_ndim=2),
    kmeans_task("s1/.*/w_gate$", stack_ndim=4),
    kmeans_task("s1/.*/w_gate$", stack_dim=2),
    {"scheme": "topk", "pattern": "w_gate$", "kappa_divisor": 10,
     "stack_ndim": 1},
    kmeans_task("no_such_leaf$"),
], ids=["kmeans-not-per-leaf", "dense-leaf-has-no-expert-axis",
        "no-item-axis-left", "misspelt-key", "topk-stack", "no-match"])
def test_task_the_program_cannot_take_is_refused(task):
    cfg = reduced_config(get_config("deepseek-moe-16b"))
    with pytest.raises(ValueError, match="task 0"):
        lcjob.program_tasks({"tasks": [task]}, cfg)
