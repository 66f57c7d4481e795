"""Batched low-rank C-step engine + the matmul-only dispatch solvers.

Contract under test (docs/architecture.md "The batched low-rank
solver"):

* ``lowrank_rsvd``/``rank_select`` solve a packed (items, m, n) group
  with matmuls + the Jacobi finisher only — no LAPACK custom call — so
  the group shards under plain GSPMD (``shard_mode == "gspmd"``, no
  shard_map workaround);
* rank and α are traced per-item operands: mixed-rank LowRank tasks and
  mixed-α RankSelection tasks pack into ONE group, factors padded to
  the group R_max (``pack_thetas_padded``) and sliced back per task;
* per-item sketch keys come from ``CompressionTask.item_keys`` —
  identical on the grouped and per-task paths, distinct per item,
  stable across reruns;
* the batched ℓ1 solvers (``project_l1_ball``, ``soft_threshold``) and
  mixed-K k-means (padded codebooks + per-item valid counts) are
  bit-identical to the legacy per-value paths on the jnp backend.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AsIs, AsStacked, AsVector, CompressionTask, \
    LCAlgorithm
from repro.core.grouping import solve_task
from repro.core.schemes import (
    AdaptiveQuantization, ConstraintL1Pruning, LowRank, PenaltyL1Pruning,
    RankSelection, project_l1_ball)
from repro.kernels import dispatch
from repro.kernels.lowrank import lowrank as lk
from repro.kernels.lowrank import ops as lops
from repro.kernels.lowrank import ref as lref
from repro.kernels.prune import ops as pops

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _decaying_stack(n_items, m, n, base=0.85, floor=3e-2, seed=7):
    """Random matrices with a controlled decaying spectrum — the regime
    randomized SVD is built for (and the bench suite uses)."""
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 2)
    u, _ = jnp.linalg.qr(jax.random.normal(ks[0], (n_items, m, m)))
    v, _ = jnp.linalg.qr(jax.random.normal(ks[1], (n_items, n, n)))
    k = min(m, n)
    sig = base ** jnp.arange(k, dtype=jnp.float32) + floor
    return jnp.einsum("imk,k,ink->imn", u[:, :, :k], sig, v[:, :, :k])


def _item_keys(n, seed=3):
    base = jax.random.fold_in(KEY, seed)
    return jax.vmap(lambda j: jax.random.fold_in(base, j))(jnp.arange(n))


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
def test_registry_has_matmul_only_solvers():
    table = dispatch.solver_table()
    for name in ("lowrank_rsvd", "rank_select", "project_l1_ball",
                 "soft_threshold"):
        assert table[name] == ("jnp",), (name, table.get(name))


def test_backend_gap_serves_interpret_requests_with_jnp():
    """jnp-only solvers have no kernel to emulate: an interpret/pallas
    request resolves to the same batched jnp program (honest gap rule),
    never to the vmap fallback."""
    for req in ("interpret", "pallas", "jnp", "auto"):
        fn, backend = dispatch.lookup("lowrank_rsvd", req)
        assert fn is lops.lowrank_rsvd_batched and backend == "jnp", req


# ----------------------------------------------------------------------
# Jacobi finisher (the matmul-only eigh)
# ----------------------------------------------------------------------
def test_jacobi_eigh_matches_lapack():
    a = jax.random.normal(jax.random.fold_in(KEY, 1), (3, 18, 30))
    g = jnp.einsum("ikn,iln->ikl", a, a)
    lam, v = lk.jacobi_eigh_batched(g, sweeps=10)
    lam_ref = np.sort(np.linalg.eigvalsh(np.asarray(g)),
                      axis=-1)[:, ::-1]
    scale = lam_ref.max()
    np.testing.assert_allclose(np.asarray(lam), lam_ref,
                               atol=1e-4 * scale)
    # eigenvector quality: V diag(λ) Vᵀ reconstructs G
    rec = jnp.einsum("ikl,il,iml->ikm", v, lam, v)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(g),
                               atol=1e-4 * scale)


def test_jacobi_eigh_zero_matrix_and_odd_k_are_safe():
    lam, v = lk.jacobi_eigh_batched(jnp.zeros((2, 7, 7)), sweeps=4)
    assert not bool(jnp.any(jnp.isnan(lam)))
    assert not bool(jnp.any(jnp.isnan(v)))
    np.testing.assert_array_equal(np.asarray(lam), 0.0)


def test_newton_schulz_orthonormalizes():
    """The alternative (orth="newton_schulz") range-finder
    orthogonalization: QᵀQ ≈ I, zero items stay zero, and the rsvd
    driver reaches the same reconstruction quality ballpark."""
    y = jax.random.normal(jax.random.fold_in(KEY, 5), (3, 120, 24))
    q = lk.newton_schulz_orthonormalize(y)
    g = jnp.einsum("imk,iml->ikl", q, q)
    assert float(jnp.max(jnp.abs(g - jnp.eye(24)))) < 1e-4
    qz = lk.newton_schulz_orthonormalize(jnp.zeros((2, 16, 4)))
    assert not bool(jnp.any(jnp.isnan(qz)))
    assert float(jnp.sum(qz ** 2)) == 0.0

    w = _decaying_stack(3, 96, 72, seed=19)
    rank = jnp.array([4, 8, 16], jnp.int32)
    u, v = lops.lowrank_rsvd_batched(w, rank, _item_keys(3), r_max=16,
                                     orth="newton_schulz")
    d = jnp.sum((w - jnp.einsum("imk,ink->imn", u, v)) ** 2,
                axis=(1, 2))
    d_exact = lref.tail_distortion_ref(w, rank)
    rel = (np.asarray(d) - np.asarray(d_exact)) / np.asarray(d_exact)
    assert np.all(rel <= 1e-3), rel      # documented: looser than jacobi


# ----------------------------------------------------------------------
# batched rsvd vs the exact-SVD oracle
# ----------------------------------------------------------------------
def test_rsvd_batched_distortion_within_1e4_of_exact():
    w = _decaying_stack(4, 96, 72)
    rank = jnp.array([4, 8, 12, 16], jnp.int32)
    u, v = lops.lowrank_rsvd_batched(w, rank, _item_keys(4), r_max=16)
    d = jnp.sum((w - jnp.einsum("imk,ink->imn", u, v)) ** 2,
                axis=(1, 2))
    d_exact = lref.tail_distortion_ref(w, rank)
    rel = (np.asarray(d) - np.asarray(d_exact)) / np.asarray(d_exact)
    assert np.all(rel <= 1e-4), rel
    # factors are masked: columns at/after each item's rank are zero
    mask = np.arange(16)[None, :] >= np.asarray(rank)[:, None]
    assert float(jnp.sum(jnp.abs(u) * mask[:, None, :])) == 0.0


def test_rsvd_batched_recovers_exactly_lowrank_matrices():
    ks = jax.random.split(KEY, 2)
    a = jax.random.normal(ks[0], (3, 64, 6))
    b = jax.random.normal(ks[1], (3, 6, 48))
    w = a @ b
    rank = jnp.array([6, 8, 12], jnp.int32)
    u, v = lops.lowrank_rsvd_batched(w, rank, _item_keys(3), r_max=12)
    rec = jnp.einsum("imk,ink->imn", u, v)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(w),
                               atol=2e-3)


def test_rsvd_batched_zero_item_yields_zero_factors():
    w = _decaying_stack(3, 40, 30).at[1].set(0.0)
    u, v = lops.lowrank_rsvd_batched(w, jnp.array([4, 4, 4]),
                                     _item_keys(3), r_max=4)
    assert not bool(jnp.any(jnp.isnan(u))) and \
        not bool(jnp.any(jnp.isnan(v)))
    assert float(jnp.sum(u[1] ** 2) + jnp.sum(v[1] ** 2)) == 0.0


# ----------------------------------------------------------------------
# mixed-rank LowRank groups through the full C step
# ----------------------------------------------------------------------
def _lowrank_setup(ranks=(4, 8, 12, 16), m=96, n=72):
    w = _decaying_stack(len(ranks), m, n, seed=11)
    params = {f"l{i}": w[i] for i in range(len(ranks))}
    tasks = lambda: [CompressionTask(f"lr{i}", f"^l{i}$", AsIs(),
                                     LowRank(r))
                     for i, r in enumerate(ranks)]
    return params, tasks


def test_mixed_rank_tasks_pack_into_one_group():
    """rank ∈ {4,8,12,16} → four groups without dispatch (rank is in
    group_key), ONE group with it (rank rides as a per-item operand,
    factors pad to R_max=16 and slice back per task)."""
    params, tasks = _lowrank_setup()
    lc_off = LCAlgorithm(tasks(), [1e-2], cstep_backend="off")
    lc_on = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp")
    assert len(lc_off.group_summary(params)) == 4
    (g,) = lc_on.group_summary(params)
    assert g["grouped"] and g["solver"] == "lowrank_rsvd"
    assert g["backend"] == "jnp" and g["items"] == 4

    st = lc_on.c_step(params, lc_on.init(params))
    for i, r in enumerate((4, 8, 12, 16)):
        th = st["tasks"][f"lr{i}"]["theta"]
        # Θ keeps each task's own shapes (padding sliced back off)
        assert th["u"].shape == (96, r) and th["v"].shape == (72, r)
        d = float(jnp.sum((params[f"l{i}"] - th["u"] @ th["v"].T) ** 2))
        d_exact = float(lref.tail_distortion_ref(
            params[f"l{i}"][None], jnp.array([r]))[0])
        assert d <= d_exact * (1 + 1e-4), (i, d, d_exact)


def test_lowrank_grouped_matches_pertask_dispatch():
    """Uniform-rank tasks: the grouped launch and the per-task solver
    path see the same R_max and the same per-item keys, so the factors
    agree to float tolerance (batched-vs-single matmul ordering)."""
    params, _ = _lowrank_setup(ranks=(8, 8, 8), m=64, n=48)
    tasks = lambda: [CompressionTask(f"lr{i}", f"^l{i}$", AsIs(),
                                     LowRank(8)) for i in range(3)]
    lcg = LCAlgorithm(tasks(), [1e-2], group_tasks=True,
                      cstep_backend="jnp")
    lcp = LCAlgorithm(tasks(), [1e-2], group_tasks=False,
                      cstep_backend="jnp")
    sg = lcg.c_step(params, lcg.init(params))
    sp = lcp.c_step(params, lcp.init(params))
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(sg["tasks"][f"lr{i}"]["theta"]["u"]),
            np.asarray(sp["tasks"][f"lr{i}"]["theta"]["u"]),
            atol=2e-5, err_msg=f"lr{i}")


def test_lowrank_randomized_false_keeps_exact_path():
    params, _ = _lowrank_setup(ranks=(4, 8), m=32, n=24)
    tasks = [CompressionTask(f"lr{i}", f"^l{i}$", AsIs(),
                             LowRank(4 * (i + 1), randomized=False))
             for i in range(2)]
    lc = LCAlgorithm(tasks, [1e-2], cstep_backend="jnp")
    summary = lc.group_summary(params)
    assert len(summary) == 2                 # rank stays in the identity
    assert all(g["solver"] is None for g in summary)


# ----------------------------------------------------------------------
# sketch keys: deterministic, per-item, path-stable
# ----------------------------------------------------------------------
def test_item_keys_distinct_and_deterministic():
    t1 = CompressionTask("a", "^a$", AsIs(), LowRank(4))
    t2 = CompressionTask("b", "^b$", AsIs(), LowRank(4))
    k1, k2 = t1.item_keys(3), t2.item_keys(3)
    # distinct across tasks and across items within a task
    seen = {tuple(np.asarray(k)) for k in list(k1) + list(k2)}
    assert len(seen) == 6
    # stable across calls (reruns are reproducible)
    np.testing.assert_array_equal(np.asarray(k1),
                                  np.asarray(t1.item_keys(3)))


def test_lowrank_cstep_rerun_is_bit_identical():
    """The sketch is keyed, not clocked: re-running the same C step on
    the same inputs reproduces Θ bit-for-bit."""
    params, tasks = _lowrank_setup()
    lc = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp")
    st = lc.init(params)
    s1 = lc.c_step(params, st)
    s2 = lc.c_step(params, st)
    for name in s1["tasks"]:
        np.testing.assert_array_equal(
            np.asarray(s1["tasks"][name]["theta"]["u"]),
            np.asarray(s2["tasks"][name]["theta"]["u"]), err_msg=name)


# ----------------------------------------------------------------------
# rank selection: mixed α, one group, identical ranks
# ----------------------------------------------------------------------
def test_rank_select_mixed_alpha_one_group_identical_ranks():
    w = _decaying_stack(4, 80, 60, seed=13)
    params = {f"l{i}": w[i] for i in range(4)}
    alphas = (1e-4, 3e-4, 1e-3, 3e-3)

    def tasks():
        return [CompressionTask(f"rs{i}", f"^l{i}$", AsIs(),
                                RankSelection(alpha=a, max_rank=24))
                for i, a in enumerate(alphas)]

    lc_on = LCAlgorithm(tasks(), [1.0], cstep_backend="jnp")
    lc_off = LCAlgorithm(tasks(), [1.0], cstep_backend="off")
    (g,) = lc_on.group_summary(params)
    assert g["solver"] == "rank_select" and g["items"] == 4
    assert len(lc_off.group_summary(params)) == 4   # α splits legacy

    s_on = lc_on.c_step(params, lc_on.init(params))
    s_off = lc_off.c_step(params, lc_off.init(params))
    for i in range(4):
        r_on = int(s_on["tasks"][f"rs{i}"]["theta"]["rank"])
        r_off = int(s_off["tasks"][f"rs{i}"]["theta"]["rank"])
        assert r_on == r_off, (i, r_on, r_off)
        # ‖W − ΔΘ‖ parity at the (identical) selected rank
        d_on = float(jnp.sum((params[f"l{i}"]
                              - lc_on.tasks[i].scheme_decompress(
                                  s_on["tasks"][f"rs{i}"]["theta"])) ** 2))
        d_off = float(jnp.sum((params[f"l{i}"]
                               - lc_off.tasks[i].scheme_decompress(
                                   s_off["tasks"][f"rs{i}"]["theta"])) ** 2))
        assert d_on <= d_off * (1 + 1e-4) + 1e-6, (i, d_on, d_off)


def test_rank_select_zero_item_selects_rank_zero():
    """A zero matrix in a stacked rank-selection task must come back
    rank 0 with zero factors — and no NaNs anywhere (the mesh-padding
    lanes hit the same code path)."""
    w = jnp.stack([_decaying_stack(1, 32, 24, seed=17)[0],
                   jnp.zeros((32, 24))])
    params = {"w": w}
    lc = LCAlgorithm(
        [CompressionTask("rs", "^w$", AsStacked("matrix"),
                         RankSelection(alpha=2e-3, max_rank=12))],
        [1.0], cstep_backend="jnp")
    st = lc.c_step(params, lc.init(params))
    th = st["tasks"]["rs"]["theta"]
    assert not bool(jnp.any(jnp.isnan(th["u"])))
    assert int(th["rank"][1]) == 0
    assert float(jnp.sum(th["u"][1] ** 2)) == 0.0
    assert int(th["rank"][0]) > 0


def test_rank_select_unbounded_keeps_exact_path():
    """max_rank=None needs the full spectrum — the batched sketch
    solver must not engage (describe_groups reports the vmap path)."""
    params = {"l0": jax.random.normal(KEY, (32, 24))}
    lc = LCAlgorithm(
        [CompressionTask("rs", "^l0$", AsIs(), RankSelection(alpha=1e-3))],
        [1.0], cstep_backend="jnp")
    (g,) = lc.group_summary(params)
    assert g["solver"] is None


def test_rank_selection_bits_flops_traced_safe():
    """Regression: bits()/flops() called float() on θ["rank"] — a
    traced device scalar inside jitted reporting paths — and crashed
    with a TracerConversionError. They must be jnp-traceable AND still
    agree with the host-side values."""
    s = RankSelection(alpha=1e-3, max_rank=12)
    w = jax.random.normal(KEY, (32, 24))
    th = s.compress(w, None, mu=1.0)

    @jax.jit
    def report(theta):
        return s.bits(theta), s.flops(theta, (32, 24))

    bits_t, flops_t = report(th)              # must not raise
    r = int(th["rank"])
    assert float(bits_t) == pytest.approx(
        r * (32 + 24) * 32 + np.ceil(np.log2(12 + 1)))
    assert float(flops_t) == pytest.approx(2.0 * r * (32 + 24))


# ----------------------------------------------------------------------
# batched ℓ1 solvers
# ----------------------------------------------------------------------
def test_project_l1_ball_batched_matches_pertask():
    w = jax.random.normal(jax.random.fold_in(KEY, 31), (4, 257))
    # row 3 is inside its ball → must pass through bit-identically
    radius = jnp.array([3.0, 10.0, 50.0, 1e6], jnp.float32)
    out = pops.project_l1_ball_batched(w, radius)
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            np.asarray(project_l1_ball(w[i], float(radius[i]))),
            err_msg=f"row {i}")
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(w[3]))


def test_l1_constraint_mixed_radius_one_group_bitwise():
    params = {f"v{i}": jax.random.normal(jax.random.fold_in(KEY, 41 + i),
                                         (300,)) for i in range(3)}
    tasks = lambda: [CompressionTask(f"c{i}", f"^v{i}$", AsVector(),
                                     ConstraintL1Pruning(kappa=3.0 * (i + 1)))
                     for i in range(3)]
    lc_on = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp")
    lc_off = LCAlgorithm(tasks(), [1e-2], cstep_backend="off")
    assert len(lc_on.group_summary(params)) == 1
    assert lc_on.group_summary(params)[0]["solver"] == "project_l1_ball"
    assert len(lc_off.group_summary(params)) == 3
    s_on = lc_on.c_step(params, lc_on.init(params))
    s_off = lc_off.c_step(params, lc_off.init(params))
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(s_on["tasks"][f"c{i}"]["theta"]["theta"]),
            np.asarray(s_off["tasks"][f"c{i}"]["theta"]["theta"]),
            err_msg=f"c{i}")


def test_penalty_l1_mixed_alpha_one_group_bitwise():
    params = {f"v{i}": jax.random.normal(jax.random.fold_in(KEY, 51 + i),
                                         (256,)) for i in range(3)}
    tasks = lambda: [CompressionTask(f"p{i}", f"^v{i}$", AsVector(),
                                     PenaltyL1Pruning(alpha=0.02 * (i + 1)))
                     for i in range(3)]
    lc_on = LCAlgorithm(tasks(), [0.5], cstep_backend="jnp")
    lc_off = LCAlgorithm(tasks(), [0.5], cstep_backend="off")
    assert len(lc_on.group_summary(params)) == 1
    s_on = lc_on.c_step(params, lc_on.init(params))
    s_off = lc_off.c_step(params, lc_off.init(params))
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(s_on["tasks"][f"p{i}"]["theta"]["theta"]),
            np.asarray(s_off["tasks"][f"p{i}"]["theta"]["theta"]),
            err_msg=f"p{i}")


# ----------------------------------------------------------------------
# mixed-K quantization groups (padded codebooks + valid counts)
# ----------------------------------------------------------------------
def _mixed_k_setup():
    params = {f"v{i}": jax.random.normal(jax.random.fold_in(KEY, 61 + i),
                                         (512,)) for i in range(3)}
    tasks = lambda: [CompressionTask(f"q{i}", f"^v{i}$", AsVector(),
                                     AdaptiveQuantization(k=2 ** (i + 1),
                                                          iters=8))
                     for i in range(3)]
    return params, tasks


def test_mixed_k_quant_one_group_bitwise_vs_off():
    """K ∈ {2,4,8} → one group under dispatch (padded codebooks,
    per-item valid counts); each task's codebook/assignments must be
    bit-identical to the per-value legacy path on the jnp backend —
    the masked (K_max + inf-padding) Lloyd loop IS the K_i loop."""
    params, tasks = _mixed_k_setup()
    lc_on = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp")
    lc_off = LCAlgorithm(tasks(), [1e-2], cstep_backend="off")
    assert len(lc_on.group_summary(params)) == 1
    assert len(lc_off.group_summary(params)) == 3
    s_on = lc_on.c_step(params, lc_on.init(params))
    s_off = lc_off.c_step(params, lc_off.init(params))
    for i in range(3):
        th_on = s_on["tasks"][f"q{i}"]["theta"]
        th_off = s_off["tasks"][f"q{i}"]["theta"]
        assert th_on.codebook.shape == (2 ** (i + 1),)  # sliced back
        np.testing.assert_array_equal(np.asarray(th_on.codebook),
                                      np.asarray(th_off.codebook),
                                      err_msg=f"q{i} codebook")
        np.testing.assert_array_equal(np.asarray(th_on.assign),
                                      np.asarray(th_off.assign),
                                      err_msg=f"q{i} assign")


def test_mixed_k_kmeans_interpret_kernel_masks_levels():
    """The items-grid kernel path must honor the per-item valid counts
    too: padded (+inf) levels never get assignments, and the live
    codebook entries agree with the jnp solve within the documented
    tolerance."""
    w = jax.random.normal(jax.random.fold_in(KEY, 71), (3, 4096))
    k_max = 8
    cb0 = jnp.sort(jax.random.normal(jax.random.fold_in(KEY, 72),
                                     (3, k_max)), axis=-1)
    kvalid = jnp.array([2, 4, 8], jnp.int32)
    from repro.kernels.kmeans import ops as kops
    cb_j, as_j = kops.kmeans_batched(w, cb0, kvalid, iters=6, impl="jnp")
    cb_k, as_k = kops.kmeans_batched(w, cb0, kvalid, iters=6,
                                     impl="interpret")
    for i, kv in enumerate((2, 4, 8)):
        assert int(jnp.max(as_j[i])) < kv
        assert int(jnp.max(as_k[i])) < kv
        np.testing.assert_allclose(np.asarray(cb_j[i, :kv]),
                                   np.asarray(cb_k[i, :kv]), atol=1e-3)
        assert bool(jnp.all(jnp.isinf(cb_j[i, kv:])))


# ----------------------------------------------------------------------
# mesh: low-rank groups shard under plain GSPMD (no shard_map
# workaround) — 1-device in-process, 4 real devices in a subprocess
# ----------------------------------------------------------------------
def test_lowrank_group_under_mesh_uses_gspmd_and_matches_no_mesh():
    from repro.launch.mesh import make_cstep_mesh
    params, tasks = _lowrank_setup()
    lc0 = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp")
    lcm = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp",
                      mesh=make_cstep_mesh())
    (g,) = lcm.group_summary(params)
    assert g["spec"] is not None
    assert g["shard_mode"] == "gspmd"        # workaround bypassed
    s0 = lc0.c_step(params, lc0.init(params))
    sm = lcm.c_step(params, lcm.init(params))
    for name in s0["tasks"]:
        np.testing.assert_allclose(
            np.asarray(s0["tasks"][name]["theta"]["u"]),
            np.asarray(sm["tasks"][name]["theta"]["u"]),
            atol=1e-5, err_msg=name)


def test_quant_group_under_mesh_still_reports_shard_map():
    """The honest counterpoint: kernel-dispatched schemes whose solver
    is NOT custom-call-free keep the shard_map workaround."""
    from repro.launch.mesh import make_cstep_mesh
    params, tasks = _mixed_k_setup()
    lcm = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp",
                      mesh=make_cstep_mesh())
    (g,) = lcm.group_summary(params)
    assert g["shard_mode"] == "shard_map"


def test_lowrank_gspmd_multidevice_subprocess():
    """A packed mixed-rank group on a real 4-device data mesh — sharded
    under plain GSPMD (incl. a padded 6→8 lane) — matches mesh=None."""
    script = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import AsIs, CompressionTask, LCAlgorithm
from repro.core.schemes import LowRank
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

assert jax.device_count() == 4, jax.device_count()
KEY = jax.random.PRNGKey(0)
ks = jax.random.split(KEY, 2)
u, _ = jnp.linalg.qr(jax.random.normal(ks[0], (6, 48, 48)))
v, _ = jnp.linalg.qr(jax.random.normal(ks[1], (6, 36, 36)))
sig = 0.85 ** jnp.arange(36, dtype=jnp.float32) + 3e-2
w = jnp.einsum("imk,k,ink->imn", u[:, :, :36], sig, v)
params = {f"l{i}": w[i] for i in range(6)}
ranks = (2, 4, 6, 8, 10, 12)

def tasks():
    return [CompressionTask(f"lr{i}", f"^l{i}$", AsIs(), LowRank(r))
            for i, r in enumerate(ranks)]

mesh = make_mesh((4,), ("data",))
lcm = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp", mesh=mesh)
lc0 = LCAlgorithm(tasks(), [1e-2], cstep_backend="jnp")
(g,) = lcm.group_summary(params)
assert g["spec"] == P("data"), g
assert g["padding"] == 2, g                  # 6 items -> 8 lanes
assert g["shard_mode"] == "gspmd", g
sm = lcm.c_step(params, lcm.init(params))
s0 = lc0.c_step(params, lc0.init(params))
for name in s0["tasks"]:
    np.testing.assert_allclose(
        np.asarray(sm["tasks"][name]["theta"]["u"]),
        np.asarray(s0["tasks"][name]["theta"]["u"]),
        atol=1e-5, err_msg=name)
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
