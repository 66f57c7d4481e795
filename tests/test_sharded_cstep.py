"""Mesh-sharded grouped C step.

The grouped engine may shard each group's packed item axis over the
mesh's data axis (``"items"`` rule in distributed/sharding.py). The
contract is strict: ``mesh=None`` and every mesh configuration —
including item counts that need padding, singleton groups, and
non-groupable schemes — produce bit-identical LC state.

The pytest process owns one CPU device, so the real multi-device runs
spawn subprocesses with ``--xla_force_host_platform_device_count=8``
(same pattern as test_distributed_integration).
"""
import os
import subprocess
import sys

import jax
import numpy as np

from repro.core import AsVector, CompressionTask, LCAlgorithm
from repro.core.grouping import describe_groups
from repro.core.schemes import AdaptiveQuantization, ConstraintL0Pruning
from repro.distributed.sharding import items_partition
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(0)


class _FakeMesh:
    """Shape-only mesh stand-in (items_partition reads names + sizes)."""

    def __init__(self, sizes: dict):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()))


# ----------------------------------------------------------------------
# items_partition: divisibility, padding, fallback
# ----------------------------------------------------------------------
def test_items_partition_divisible():
    mesh = _FakeMesh({"data": 4, "model": 2})
    assert items_partition(8, mesh) == ("data", 0)
    assert items_partition(4, mesh) == ("data", 0)


def test_items_partition_pads_to_axis():
    mesh = _FakeMesh({"data": 4, "model": 2})
    assert items_partition(5, mesh) == ("data", 3)
    assert items_partition(2, mesh) == ("data", 2)
    # already-divisible counts never pad
    assert items_partition(12, mesh) == ("data", 0)


def test_items_partition_no_pad_requires_divisibility():
    mesh = _FakeMesh({"data": 4, "model": 2})
    assert items_partition(5, mesh, allow_pad=False) == (None, 0)
    assert items_partition(8, mesh, allow_pad=False) == ("data", 0)


def test_items_partition_missing_axis_replicates():
    mesh = _FakeMesh({"model": 4})
    assert items_partition(8, mesh) == (None, 0)


def test_items_partition_respects_custom_rules():
    mesh = _FakeMesh({"pod": 2, "data": 2, "model": 2})
    rules = {"items": [("pod", "data"), ("data",), ()]}
    assert items_partition(8, mesh, rules) == (("pod", "data"), 0)
    assert items_partition(3, mesh, rules) == (("pod", "data"), 1)


# ----------------------------------------------------------------------
# describe_groups: resolved spec + padding fields
# ----------------------------------------------------------------------
def _four_prune_tasks(n=4, p=64):
    params = {f"l{i}": jax.random.normal(jax.random.fold_in(KEY, i), (p,))
              for i in range(n)}
    tasks = [CompressionTask(f"pr{i}", f"^l{i}$", AsVector(),
                             ConstraintL0Pruning(kappa=8),
                             paths=[f"l{i}"])
             for i in range(n)]
    xs = {t.name: params[t.paths[0]] for t in tasks}
    return tasks, xs


def test_describe_groups_reports_spec_and_padding():
    tasks, xs = _four_prune_tasks(n=3)
    mesh = _FakeMesh({"data": 2, "model": 4})
    (g,) = describe_groups(tasks, xs, mesh=mesh)
    assert g["grouped"] and g["items"] == 3
    assert g["spec"] == P("data")
    assert g["padding"] == 1  # 3 items over a 2-way data axis


def test_describe_groups_no_mesh_fields_default():
    tasks, xs = _four_prune_tasks(n=3)
    (g,) = describe_groups(tasks, xs)
    assert g["spec"] is None and g["padding"] == 0


def test_describe_groups_singleton_has_no_spec():
    """Singleton groups run the per-task path, so no sharding spec even
    with a mesh bound."""
    tasks, xs = _four_prune_tasks(n=1)
    mesh = _FakeMesh({"data": 2, "model": 4})
    (g,) = describe_groups(tasks, xs, mesh=mesh)
    assert not g["grouped"]
    assert g["spec"] is None and g["padding"] == 0


def test_describe_groups_replication_fallback_spec_is_none():
    """A mesh without a usable "items" axis falls back to replication —
    the report must say 'not sharded' (None), not PartitionSpec(None)."""
    tasks, xs = _four_prune_tasks(n=4)
    mesh = _FakeMesh({"model": 4})
    (g,) = describe_groups(tasks, xs, mesh=mesh)
    assert g["grouped"]
    assert g["spec"] is None and g["padding"] == 0


def test_group_summary_ignores_mesh_on_pertask_path():
    """group_tasks=False executes the unsharded per-task C step, so the
    summary must not report a layout that is never applied."""
    params = {f"l{i}": jax.random.normal(jax.random.fold_in(KEY, i), (64,))
              for i in range(4)}
    tasks = [CompressionTask(f"pr{i}", f"^l{i}$", AsVector(),
                             ConstraintL0Pruning(kappa=8))
             for i in range(4)]
    mesh = make_mesh((1, 1), ("data", "model"))
    lc = LCAlgorithm(tasks, [1e-2], group_tasks=False, mesh=mesh)
    (g,) = lc.group_summary(params)
    assert g["spec"] is None and g["padding"] == 0


def test_group_summary_threads_mesh():
    params = {f"l{i}": jax.random.normal(jax.random.fold_in(KEY, i), (64,))
              for i in range(4)}
    tasks = [CompressionTask(f"pr{i}", f"^l{i}$", AsVector(),
                             ConstraintL0Pruning(kappa=8))
             for i in range(4)]
    mesh = make_mesh((1, 1), ("data", "model"))
    lc = LCAlgorithm(tasks, [1e-2], mesh=mesh)
    (g,) = lc.group_summary(params)
    assert g["spec"] == P("data") and g["padding"] == 0


# ----------------------------------------------------------------------
# single-device mesh: the sharded code path must already be exact
# ----------------------------------------------------------------------
def _state_equal(sa, sb):
    fa = jax.tree_util.tree_leaves_with_path(sa)
    fb = jax.tree_util.tree_leaves_with_path(sb)
    assert len(fa) == len(fb)
    for (ka, va), (kb, vb) in zip(fa, fb):
        assert ka == kb
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=jax.tree_util.keystr(ka))


def _quant_prune_setup():
    params = {
        f"l{i}": {"w": jax.random.normal(jax.random.fold_in(KEY, i),
                                         (128,)),
                  "p": jax.random.normal(jax.random.fold_in(KEY, 50 + i),
                                         (96,))}
        for i in range(3)}

    def tasks():
        return (
            [CompressionTask(f"q{i}", rf"l{i}/w$", AsVector(),
                             AdaptiveQuantization(k=4, iters=5))
             for i in range(3)]
            + [CompressionTask(f"pr{i}", rf"l{i}/p$", AsVector(),
                               ConstraintL0Pruning(kappa=16))
               for i in range(3)])
    return params, tasks


def test_one_device_mesh_matches_mesh_none():
    params, tasks = _quant_prune_setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    lcm = LCAlgorithm(tasks(), [1e-2, 1.5e-2], mesh=mesh)
    lc0 = LCAlgorithm(tasks(), [1e-2, 1.5e-2])
    sm, s0 = lcm.init(params), lc0.init(params)
    for _ in range(2):
        sm = lcm.multiplier_step(params, lcm.c_step(params, sm))
        s0 = lc0.multiplier_step(params, lc0.c_step(params, s0))
    _state_equal(sm, s0)


def test_set_mesh_rebuilds_jitted_c_step():
    """A mesh bound after the first compile must still take effect —
    set_mesh rebuilds the jitted steps (the mesh is trace-time state)."""
    params, tasks = _quant_prune_setup()
    lc = LCAlgorithm(tasks(), [1e-2])
    st = lc.init(params)
    st1 = lc.c_step(params, st)                     # compiled without mesh
    before = lc._c_step
    lc.set_mesh(make_mesh((1, 1), ("data", "model")))
    assert lc._c_step is not before                 # stale cache dropped
    st2 = lc.c_step(params, st)
    _state_equal(st1, st2)
    (g, *_) = lc.group_summary(params)
    assert g["spec"] == P("data")


def test_trainer_threads_mesh_into_algorithm():
    from repro.configs import get_config, reduced_config
    from repro.data import TokenStream
    from repro.launch.mesh import make_debug_mesh
    from repro.runtime import LCTrainer

    cfg = reduced_config(get_config("phi3-mini-3.8b")).with_(pattern_reps=1)
    lc = LCAlgorithm(
        [CompressionTask("q", r"stages/.*/w_gate$", AsVector(),
                         AdaptiveQuantization(k=2, iters=5))], [1e-4])
    mesh = make_debug_mesh()
    trainer = LCTrainer(cfg, lc, TokenStream(cfg.vocab_size, 2, 16),
                        mesh=mesh)
    assert trainer.lc.mesh is mesh


# ----------------------------------------------------------------------
# real multi-device meshes (subprocess, 8 forced host devices)
# ----------------------------------------------------------------------
def _run(script: str, devices: int = 8, timeout: int = 500) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_cstep_bit_identical_on_2x4_mesh():
    """Acceptance criterion: a (2, 4) data×model mesh produces Θ/Δ(Θ)/λ
    bit-identical to mesh=None on a mixed config that covers every edge:
    a padded group (5 items over data=2), divisible groups, a LAPACK
    custom-call scheme (LowRank/SVD), a stacked view, a singleton group,
    and a non-groupable (group_key=None) scheme."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import (AsIs, AsStacked, AsVector, CompressionTask,
                        LCAlgorithm)
from repro.core.schemes import (AdaptiveQuantization, ConstraintL0Pruning,
                                LowRank)
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

class OptOutPrune(ConstraintL0Pruning):
    def group_key(self):
        return None

KEY = jax.random.PRNGKey(0)
params = {
    f"l{i}": {"w": jax.random.normal(jax.random.fold_in(KEY, i), (32, 16)),
              "p": jax.random.normal(jax.random.fold_in(KEY, 100 + i),
                                     (512,))}
    for i in range(4)}
params["stack"] = {"w": jax.random.normal(jax.random.fold_in(KEY, 999),
                                          (3, 512))}
params["solo"] = {"w": jax.random.normal(jax.random.fold_in(KEY, 55),
                                         (77,))}
params["exotic"] = {"p": jax.random.normal(jax.random.fold_in(KEY, 66),
                                           (512,))}

def tasks():
    return (
        # 2 single items + 3 stacked items = 5 over data=2 -> padding 1
        [CompressionTask(f"q{i}", rf"l{i}/w$", AsVector(),
                         AdaptiveQuantization(k=4, iters=5))
         for i in range(2)]
        + [CompressionTask("st", r"stack/w$", AsStacked("vector"),
                           AdaptiveQuantization(k=4, iters=5))]
        # 4 items over data=2 -> divisible
        + [CompressionTask(f"pr{i}", rf"l{i}/p$", AsVector(),
                           ConstraintL0Pruning(kappa=64))
           for i in range(4)]
        # LAPACK svd custom call inside the sharded region
        + [CompressionTask("lr", r"l[23]/w$", AsIs(),
                           LowRank(2, randomized=False))]
        # singleton group: unique shape -> per-task path
        + [CompressionTask("solo", r"solo/w$", AsVector(),
                           ConstraintL0Pruning(kappa=8))]
        # non-groupable: group_key None -> per-task path
        + [CompressionTask("ex", r"exotic/p$", AsVector(),
                           OptOutPrune(kappa=64))])

mesh = make_mesh((2, 4), ("data", "model"))
lcm = LCAlgorithm(tasks(), [1e-2] * 3, mesh=mesh)
lc0 = LCAlgorithm(tasks(), [1e-2] * 3)

summary = {tuple(g["tasks"]): g for g in lcm.group_summary(params)}
g_quant = summary[("q0", "q1", "st")]
assert g_quant["spec"] == P("data") and g_quant["padding"] == 1, g_quant
g_prune = summary[("pr0", "pr1", "pr2", "pr3")]
assert g_prune["spec"] == P("data") and g_prune["padding"] == 0, g_prune
g_solo = summary[("solo",)]
assert g_solo["spec"] is None and g_solo["padding"] == 0, g_solo
g_ex = summary[("ex",)]
assert g_ex["spec"] is None and not g_ex["grouped"], g_ex

sm, s0 = lcm.init(params), lc0.init(params)
params2 = jax.tree_util.tree_map(lambda x: x + 0.01 * jnp.sin(7 * x),
                                 params)
for _ in range(2):
    sm = lcm.multiplier_step(params2, lcm.c_step(params2, sm))
    s0 = lc0.multiplier_step(params2, lc0.c_step(params2, s0))
fm = jax.tree_util.tree_leaves_with_path(sm)
f0 = jax.tree_util.tree_leaves_with_path(s0)
assert len(fm) == len(f0)
for (km, vm), (k0, v0) in zip(fm, f0):
    assert km == k0
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(v0),
                                  err_msg=jax.tree_util.keystr(km))
print("bit-identical ok")
"""
    out = _run(script)
    assert "bit-identical ok" in out


def test_sharded_cstep_multipod_rule():
    """Custom rules: ("pod", "data") joint sharding on a (2, 2, 2) mesh,
    6 items -> pad 2, still bit-identical to mesh=None."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import AsVector, CompressionTask, LCAlgorithm
from repro.core.schemes import ConstraintL0Pruning
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

KEY = jax.random.PRNGKey(0)
params = {f"l{i}": jax.random.normal(jax.random.fold_in(KEY, i), (256,))
          for i in range(6)}
def tasks():
    return [CompressionTask(f"pr{i}", f"^l{i}$", AsVector(),
                            ConstraintL0Pruning(kappa=32))
            for i in range(6)]
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = {"items": [("pod", "data"), ("data",), ()]}
lcm = LCAlgorithm(tasks(), [1e-2], mesh=mesh, sharding_rules=rules)
lc0 = LCAlgorithm(tasks(), [1e-2])
(g,) = lcm.group_summary(params)
assert g["spec"] == P(("pod", "data")) and g["padding"] == 2, g
sm = lcm.c_step(params, lcm.init(params))
s0 = lc0.c_step(params, lc0.init(params))
for (km, vm), (k0, v0) in zip(jax.tree_util.tree_leaves_with_path(sm),
                              jax.tree_util.tree_leaves_with_path(s0)):
    assert km == k0
    np.testing.assert_array_equal(np.asarray(vm), np.asarray(v0),
                                  err_msg=jax.tree_util.keystr(km))
print("multipod ok")
"""
    out = _run(script)
    assert "multipod ok" in out
