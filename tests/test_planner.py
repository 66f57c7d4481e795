"""Cost-model group planner: planner-on must be bit-identical to
planner-off for every scheme family (incl. chunked launches), plans and
AOT executables must cache across LC boundaries and jit rebuilds, and
the warm-started low-rank sketches must stay inside the documented
≤1e-4 relative-distortion budget."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import cost
from repro.analysis.lint.contract import discover_scheme_classes
from repro.core import AsStacked, CompressionTask, LCAlgorithm
from repro.core.grouping import (
    _plan_multi_group, _task_solver, compile_group, describe_groups,
    grouped_compress)
from repro.core.schemes import AdaptiveQuantization, ConstraintL0Pruning
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(7)


def _family_cases():
    """(id, scheme) for the first contract example of every registered
    scheme class — the same sweep the lint layers run."""
    cases = []
    for cls in discover_scheme_classes():
        for i, ex in enumerate(cls.contract_examples()):
            cases.append(pytest.param(ex, id=f"{cls.__name__}[{i}]"))
    return cases


def _real_group(scheme, n_tasks=2, n_items=4):
    """A concrete multi-task group for one scheme instance: real
    arrays, engine-derived Θ — the executable twin of
    ``lint.hlo_rules.representative_group``."""
    item = (12, 8) if scheme.domain == "matrix" else (64,)
    group, xs, thetas = [], {}, {}
    for i in range(n_tasks):
        name = f"plan/{type(scheme).__name__}/{i}"
        t = CompressionTask(name, pattern=".",
                            view=AsStacked(scheme.domain), scheme=scheme)
        x = jax.random.normal(jax.random.fold_in(KEY, i),
                              (n_items,) + item, jnp.float32)
        group.append(t)
        xs[name] = x
        thetas[name] = t.scheme_init(x)
    return group, xs, thetas


def _compress(group, xs, thetas, planner, backend="auto", mu=1e-2):
    @jax.jit
    def step(xs, thetas):
        return grouped_compress(group, xs, thetas, jnp.float32(mu),
                                backend=backend, planner=planner)
    return step(xs, thetas)


def _assert_tree_equal(a, b, msg):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


@pytest.mark.parametrize("scheme", _family_cases())
@pytest.mark.parametrize("backend", ["auto", "off"])
def test_planner_parity_every_family(scheme, backend):
    """planner="on" must be bitwise planner-off for every scheme family
    on both dispatch modes — the planner only re-derives the static
    rule's choices off-TPU."""
    group, xs, thetas = _real_group(scheme)
    on = _compress(group, xs, thetas, "on", backend=backend)
    off = _compress(group, xs, thetas, None, backend=backend)
    _assert_tree_equal(on, off,
                       f"planner parity broken: {scheme} {backend}")


@pytest.mark.parametrize("scheme", _family_cases())
def test_chunked_solve_bit_identical(scheme):
    """A chunk budget small enough to split every group into per-item
    launches must not change a single bit: packing happens group-wide
    before the split and the solvers are per-item independent."""
    group, xs, thetas = _real_group(scheme, n_items=4)
    baseline = _compress(group, xs, thetas, None)
    old = cost.CHUNK_BUDGET_BYTES
    cost.CHUNK_BUDGET_BYTES = 1      # budget is NOT in the plan key:
    cost.clear_caches()              # drop plans made under the default
    try:
        chunked = _compress(group, xs, thetas, "on")
        counts = [t.view.item_count(xs[t.name]) for t in group]
        solver_fn, _ = _task_solver(group[0].scheme, "auto")
        plan = _plan_multi_group(group, xs, thetas, counts, solver_fn,
                                 None, None, "auto")
        assert plan.n_chunks > 1, "budget override never forced a split"
    finally:
        cost.CHUNK_BUDGET_BYTES = old
        cost.clear_caches()
    _assert_tree_equal(chunked, baseline,
                       f"chunked solve diverged: {scheme}")


def _probe_algo():
    params = {
        "qa": jnp.linspace(-1.0, 1.0, 64).reshape(4, 16),
        "qb": jnp.linspace(-3.0, 3.0, 64).reshape(4, 16),
        "pa": jnp.linspace(1.0, -1.0, 64).reshape(4, 16),
        "pb": jnp.linspace(2.0, -2.0, 64).reshape(4, 16),
    }
    tasks = [
        CompressionTask("qa", "qa", AsStacked("vector"),
                        AdaptiveQuantization(k=2, iters=2)),
        CompressionTask("qb", "qb", AsStacked("vector"),
                        AdaptiveQuantization(k=2, iters=2)),
        CompressionTask("pa", "pa", AsStacked("vector"),
                        ConstraintL0Pruning(kappa=8)),
        CompressionTask("pb", "pb", AsStacked("vector"),
                        ConstraintL0Pruning(kappa=4)),
    ]
    algo = LCAlgorithm(tasks, [1e-3, 2e-3, 4e-3], planner="on")
    return algo, params


def test_plan_cache_across_boundaries_and_rebuild():
    """≥3 identical LC boundaries + a forced jit rebuild: the plan is
    computed once per group and every later lookup hits the cache
    (zero re-plans) — the lint probe's assertion, exercised directly."""
    from repro.analysis.lint.trace_count import check_planner_cache

    cost.clear_caches()
    algo, params = _probe_algo()
    lc = algo.init(params)
    findings = check_planner_cache(algo, params, lc, boundaries=3)
    assert findings == [], [f.format() for f in findings]
    stats = cost.cache_stats()
    assert stats["plan_entries"] == 2          # quant group + prune group
    assert stats["plan_misses"] == 2
    assert stats["plan_hits"] >= 2             # the rebuild's re-trace


def test_full_lc_loop_parity_planner_on():
    """Multi-boundary LC loop (c step + multiplier step at rising μ):
    planner-on state must equal planner-off state bitwise."""
    def run(planner):
        algo, params = _probe_algo()
        algo.set_planner(planner)
        lc = algo.init(params)
        for k, mu in enumerate(algo.mu_schedule):
            lc = algo.set_mu(lc, mu, k)
            lc = algo.c_step(params, lc)
            lc = algo.multiplier_step(params, lc)
        return lc

    _assert_tree_equal(run("on"), run("off"), "LC loop planner parity")


def test_plan_key_sensitivity():
    """The cache key must miss on any signature/shape/backend/mesh/
    item-count change — and only on those."""
    sds = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    base = cost.plan_key(("quant", 4), 4, (sds,), None, "auto")
    assert base == cost.plan_key(("quant", 4), 4, (sds,), None, "auto")
    assert base != cost.plan_key(("quant", 8), 4, (sds,), None, "auto")
    assert base != cost.plan_key(("quant", 4), 8, (sds,), None, "auto")
    assert base != cost.plan_key(("quant", 4), 4, (sds,), None, "jnp")
    other = jax.ShapeDtypeStruct((4, 32), jnp.float32)
    assert base != cost.plan_key(("quant", 4), 4, (other,), None, "auto")
    mesh = make_mesh((1,), ("data",))
    assert base != cost.plan_key(("quant", 4), 4, (sds,), mesh, "auto")


def test_exec_cache_zero_retrace_across_boundaries():
    """compile_group: one compile, then cache hits only — and the
    executable's output at each μ matches the jitted engine path."""
    scheme = AdaptiveQuantization(k=2, iters=2)
    group, xs, thetas = _real_group(scheme)
    cost.clear_caches()
    compiled, arrays = compile_group(group, xs, thetas, backend="auto")
    for _ in range(3):
        compiled2, _ = compile_group(group, xs, thetas, backend="auto")
        assert compiled2 is compiled
    stats = cost.cache_stats()
    assert stats["exec_misses"] == 1
    assert stats["exec_hits"] == 3
    for mu in (1e-3, 2e-3):
        theta_packed, a_packed = compiled(jnp.float32(mu), *arrays)
        ref = _compress(group, xs, thetas, None, mu=mu)
        packed_ref = jnp.concatenate(
            [ref[t.name][1] for t in group], axis=0)
        np.testing.assert_array_equal(np.asarray(a_packed),
                                      np.asarray(packed_ref))


def test_exec_cache_miss_on_shape_and_backend_change():
    scheme = AdaptiveQuantization(k=2, iters=2)
    cost.clear_caches()
    group, xs, thetas = _real_group(scheme, n_items=2)
    compile_group(group, xs, thetas, backend="auto")
    assert cost.cache_stats()["exec_misses"] == 1
    compile_group(group, xs, thetas, backend="off")       # backend change
    assert cost.cache_stats()["exec_misses"] == 2
    group3, xs3, thetas3 = _real_group(scheme, n_items=3)  # shape change
    compile_group(group3, xs3, thetas3, backend="auto")
    assert cost.cache_stats()["exec_misses"] == 3


def test_describe_groups_reports_plan():
    algo, params = _probe_algo()
    rows = describe_groups(algo.tasks,
                           {t.name: params[t.name] for t in algo.tasks},
                           backend="auto", planner="on")
    planned = [r for r in rows if r["plan"] is not None]
    assert len(planned) == 2
    for r in planned:
        plan = r["plan"]
        assert plan["backend"] == r["backend"] == "jnp"   # CPU static rule
        assert plan["n_chunks"] == 1
        assert plan["source"] == "hlo"
        assert plan["bottleneck"] in ("compute", "memory", "collective")
        assert plan["modeled_ms"] > 0.0
    # planner-off: the field is present but unpopulated
    rows_off = describe_groups(algo.tasks,
                               {t.name: params[t.name]
                                for t in algo.tasks}, backend="auto")
    assert all(r["plan"] is None for r in rows_off)


def test_planner_arg_validation():
    algo, _ = _probe_algo()
    with pytest.raises(ValueError, match="planner"):
        LCAlgorithm(algo.tasks, [1e-3], planner="bogus")
    with pytest.raises(ValueError, match="planner"):
        algo.set_planner("maybe")


def test_detect_hardware_and_tiles():
    hw = cost.detect_hardware()
    assert hw.name == "cpu"                    # CI runs on CPU
    assert hw.ridge_intensity > 0
    # the old roofline literals survived the HardwareSpec refactor
    from repro.analysis import roofline
    assert roofline.PEAK_FLOPS == cost.TPU_V5E.peak_flops
    assert roofline.HBM_BW == cost.TPU_V5E.hbm_bw
    assert roofline.LINK_BW == cost.TPU_V5E.link_bw
    tiles = cost.gemm_tiles(4, 2048, 512, packed=True)
    assert set(tiles) == {"block_m", "block_n", "block_k"}
    assert all(v >= 8 for v in tiles.values())


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_detect_hardware_exact_kind():
    """A v5e reports "TPU v5 lite" and gets v5e's published peaks, not
    v5p's; an unknown TPU kind is an error, never a default."""
    hw = cost.detect_hardware([_Dev("tpu", "TPU v5 lite")])
    assert hw is cost.TPU_V5E
    assert (hw.peak_flops, hw.hbm_bw) == (197e12, 819e9)
    assert cost.detect_hardware([_Dev("tpu", "TPU v5")]) is cost.TPU_V5P
    assert cost.detect_hardware([_Dev("cpu", "cpu")]) is cost.CPU
    with pytest.raises(ValueError, match="TPU v99"):
        cost.detect_hardware([_Dev("tpu", "TPU v99")])


def test_chunk_and_backend_choosers():
    hw = cost.CPU
    assert cost.choose_chunks(100, 8, hw) == 1
    old = cost.CHUNK_BUDGET_BYTES
    cost.CHUNK_BUDGET_BYTES = 10
    try:
        assert cost.choose_chunks(35, 8, hw) == 4
        assert cost.choose_chunks(1 << 30, 8, hw) == 8   # ≤ n_items
    finally:
        cost.CHUNK_BUDGET_BYTES = old
    terms = {"flops": 1.0, "bytes": 1e9, "working_set_bytes": 1 << 22}
    # explicit requests are honored verbatim
    assert cost.choose_backend("interpret", "kmeans_lloyd",
                               ("jnp", "interpret"), terms, hw)[0] \
        == "interpret"
    # "auto" off-TPU is the static rule: jnp
    assert cost.choose_backend("auto", "kmeans_lloyd",
                               ("jnp", "pallas"), terms, hw)[0] == "jnp"
    # on TPU, a memory-bound group with a registered pallas kernel
    # gets the fused kernel; a compute-bound one stays on XLA
    b, _ = cost.choose_backend("auto", "kmeans_lloyd",
                               ("jnp", "pallas"), terms, cost.TPU_V5E)
    assert b == "pallas"
    hot = dict(terms, flops=1e15)
    b, fb = cost.choose_backend("auto", "kmeans_lloyd",
                                ("jnp", "pallas"), hot, cost.TPU_V5E)
    assert b == "jnp" and fb
    # off-TPU tiles stay default (bit-parity contract)
    rows, _ = cost.choose_block_rows("kmeans_lloyd", "interpret", 4,
                                     4096, 0, hw)
    assert rows is None
    rows, _ = cost.choose_block_rows("kmeans_lloyd", "pallas", 4,
                                     4096, 0, cost.TPU_V5E)
    assert rows in cost.BLOCK_ROWS_CANDIDATES


def test_warm_started_sketch_distortion_bound():
    """Warm-started range finder (previous U + thin fresh sketch, fewer
    power iterations) must stay within 1e-4 relative distortion of the
    exact truncated SVD — the budget LowRank documents."""
    from repro.kernels.lowrank.ops import (
        _warm_iters, lowrank_rsvd_batched)

    assert _warm_iters(3) == 2
    assert _warm_iters(1) == 1

    items, m, n, r = 3, 64, 48, 4
    rng = np.random.default_rng(0)
    ws = []
    for _ in range(items):
        u, _ = np.linalg.qr(rng.standard_normal((m, n)))
        v, _ = np.linalg.qr(rng.standard_normal((n, n)))
        s = 2.0 ** -np.arange(n)                 # decaying spectrum
        ws.append((u[:, :n] * s) @ v.T)
    w1 = jnp.asarray(np.stack(ws), jnp.float32)

    rank = jnp.full((items,), r, jnp.int32)
    keys = jnp.stack([jax.random.fold_in(KEY, i)
                      for i in range(items)])
    u_prev, _ = lowrank_rsvd_batched(w1, rank, keys, r_max=r)

    # "late μ": the target barely moves between C steps
    w2 = w1 + 1e-4 * jnp.asarray(
        rng.standard_normal(w1.shape), jnp.float32)
    u2, v2 = lowrank_rsvd_batched(w2, rank, keys, r_max=r, u0=u_prev)

    w2np = np.asarray(w2, np.float64)
    approx = np.asarray(u2, np.float64) @ \
        np.asarray(v2, np.float64).transpose(0, 2, 1)
    err_warm = np.sum((w2np - approx) ** 2, axis=(1, 2))
    sv = np.linalg.svd(w2np, compute_uv=False)
    err_exact = np.sum(sv[:, r:] ** 2, axis=1)
    total = np.sum(w2np ** 2, axis=(1, 2))
    excess = (err_warm - err_exact) / total
    assert np.all(excess <= 1e-4), excess
