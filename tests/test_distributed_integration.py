"""Multi-device integration tests.

The pytest process owns one CPU device, so these spawn subprocesses with
``--xla_force_host_platform_device_count`` to exercise real GSPMD
partitioning: sharded train step (data+tensor parallel, MoE shard_map
dispatch), multi-pod mesh, and numerical equivalence between 1-device
and 8-device execution of the same step.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 500) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


COMMON = """
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config, reduced_config
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, init_train_state
from repro.launch import inputs as specs_mod
"""


def test_sharded_train_step_matches_single_device():
    """Same seed, same batch: loss on a (2,4) mesh must match 1-device
    execution — GSPMD partitioning is numerics-preserving (within fp32
    reduction noise)."""
    script = COMMON + """
import dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
arch = "jamba-v0.1-52b"   # covers mamba + attention + MoE shard_map
cfg = reduced_config(get_config(arch)).with_(dtype="float32")
# no-drop capacity: per-shard vs global capacity otherwise drops
# different tokens (expected EP semantics, but breaks exact equivalence)
cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
key = jax.random.PRNGKey(0)
batch = {
  "inputs": jax.random.randint(jax.random.fold_in(key,1), (4, 16), 0, cfg.vocab_size),
  "labels": jax.random.randint(jax.random.fold_in(key,2), (4, 16), 0, cfg.vocab_size),
}
losses = {}
for shape, axes in [((1,1),("data","model")), ((2,4),("data","model"))]:
    mesh = make_mesh(shape, axes)
    with use_mesh(mesh):
        state = init_train_state(key, cfg)
        step = jax.jit(make_train_step(cfg))
        with mesh:
            new_state, metrics = step(state, batch)
        losses[str(shape)] = float(metrics["loss"])
print(json.dumps(losses))
assert abs(losses["(1, 1)"] - losses["(2, 4)"]) < 5e-3, losses
"""
    out = _run(script)
    losses = json.loads(out.strip().splitlines()[-1])
    assert abs(losses["(1, 1)"] - losses["(2, 4)"]) < 5e-3


def test_multipod_mesh_step_runs():
    """(pod, data, model) = (2, 2, 2) mesh executes a full LC train step."""
    script = COMMON + """
cfg = reduced_config(get_config("mixtral-8x7b"))
key = jax.random.PRNGKey(0)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
batch = {
  "inputs": jax.random.randint(jax.random.fold_in(key,1), (8, 16), 0, cfg.vocab_size),
  "labels": jax.random.randint(jax.random.fold_in(key,2), (8, 16), 0, cfg.vocab_size),
}
with use_mesh(mesh):
    state = init_train_state(key, cfg)
    step = jax.jit(make_train_step(cfg))
    with mesh:
        state, metrics = step(state, batch)
assert np.isfinite(float(metrics["loss"]))
print("ok", float(metrics["loss"]))
"""
    out = _run(script)
    assert "ok" in out


@pytest.mark.parametrize("devices", [1, 4])
def test_mesh_lc_trainer_compiles_each_step_once(devices):
    """Two LC steps of ``LCTrainer`` on a data mesh: the train step, the
    C step, the multiplier step and the distortion monitor each compile
    once. A multiplier λ left in GSPMD's layout, or a new μ placed apart
    from the state's, made every LC step compile them again; optimizer
    moments returned in GSPMD's own layout made the train step compile
    twice on four devices."""
    script = """
import json
import jax
from repro.configs import get_config, reduced_config
from repro.core import LCAlgorithm, exponential_mu_schedule
from repro.data import TokenStream
from repro.launch.mesh import make_cstep_mesh
from repro.launch.train import default_tasks
from repro.runtime import LCTrainer, TrainerConfig
cfg = reduced_config(get_config("xlstm-125m"))
lc = LCAlgorithm(default_tasks(cfg, "prune"),
                 exponential_mu_schedule(1e-4, 1.2, 2))
tr = LCTrainer(cfg, lc, TokenStream(cfg.vocab_size, 4, 16),
               mesh=make_cstep_mesh(), tcfg=TrainerConfig(steps_per_l=2))
tr.run(jax.random.PRNGKey(0))
steps = {n: getattr(lc, "_" + n)
         for n in ("c_step", "mult_step", "shifted_distortion")}
steps["train_step"] = tr._train_step
print(json.dumps({n: f._cache_size() for n, f in steps.items()}))
"""
    out = _run(script, devices=devices)
    compiles = json.loads(out.strip().splitlines()[-1])
    once = {"train_step", "c_step", "mult_step", "shifted_distortion"}
    assert {n: compiles[n] for n in once} == dict.fromkeys(once, 1), \
        compiles


def test_mesh_lc_trainer_step_is_data_parallel():
    """``LCTrainer``'s train step on a 4-way data mesh splits the batch:
    each device runs about a quarter of the one-device step's FLOPs, and
    in float32 the step's loss and gradient norm match one device's. The
    batch once went in unsharded, and every device ran the whole step."""
    script = """
import json
import jax
from repro.configs import get_config, reduced_config
from repro.core import LCAlgorithm, exponential_mu_schedule
from repro.data import TokenStream
from repro.distributed.sharding import use_mesh
from repro.launch.mesh import make_cstep_mesh
from repro.launch.train import default_tasks
from repro.runtime import LCTrainer
cfg = reduced_config(get_config("xlstm-125m")).with_(dtype="float32")
out = {}
for name, mesh in (("mesh", make_cstep_mesh()), ("one", None)):
    lc = LCAlgorithm(default_tasks(cfg), exponential_mu_schedule(1e-4, 1.2, 1))
    tr = LCTrainer(cfg, lc, TokenStream(cfg.vocab_size, 8, 16), mesh=mesh)
    state = tr.init_state(jax.random.PRNGKey(0))
    batch = tr.data.batch_at(0)
    _, m = tr.train_step(state, batch)
    with use_mesh(mesh):
        c = tr._train_step.lower(state, tr.shard_batch(batch)).compile()
    out[name] = {"loss": float(m["loss"]),
                 "grad_norm": float(m["grad_norm"]),
                 "flops": c.cost_analysis()["flops"]}
print(json.dumps(out))
"""
    out = json.loads(_run(script, devices=4).strip().splitlines()[-1])
    mesh, one = out["mesh"], out["one"]
    assert mesh["flops"] < 0.4 * one["flops"], out
    for k in ("loss", "grad_norm"):
        assert abs(mesh[k] - one[k]) <= 1e-5 * abs(one[k]), out


def test_dryrun_cell_subprocess():
    """The real dry-run path (512 fake devices) for the cheapest cell."""
    script = """
import sys
sys.argv = ["dryrun", "--arch", "xlstm-125m", "--shape", "decode_32k",
            "--out", "/tmp/test_dryrun_cells", "--force"]
from repro.launch import dryrun
try:
    dryrun.main()
except SystemExit as e:
    assert e.code == 0, "dry-run cell failed"
import json, glob
f = glob.glob("/tmp/test_dryrun_cells/*.json")[0]
d = json.load(open(f))
assert d["status"] == "ok", d
print("bottleneck:", d["bottleneck"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "bottleneck:" in out.stdout
