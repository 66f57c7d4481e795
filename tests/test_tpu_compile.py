"""Compile the main path's Pallas kernels for a TPU v5e without a chip.

Each test lowers one kernel with ``interpret=False`` at xlstm-125m widths
against a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what interpret mode accepts: blocks that break
the (8, 128) tiling, layouts Mosaic cannot relayout, VMEM overuse. The
kernels are steered directly because their ops wrappers ask
``jax.default_backend()`` and take the CPU branch here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import AsStacked, AsVector, CompressionTask, LCAlgorithm
from repro.core.schemes import AdaptiveQuantization, ConstraintL0Pruning
from repro.kernels import dispatch
from repro.kernels.kmeans.kmeans import kmeans_assign_moments_batched
from repro.kernels.prune.prune import count_above_batched, mask_apply_batched
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_packed

D_MODEL, D_UP = 768, 1536          # xlstm-125m: d_model, mLSTM inner width
ITEMS = 10                         # one packed k-means group: 5 tasks x 2
ITEM = D_MODEL * D_UP              # one stacked layer's projection, flat
K = 16                             # 4-bit codebook
SLOTS = 8                          # decode rows, padded to one sublane tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mesh4(topo, one_chip):
    from repro.launch.mesh import make_mesh
    return make_mesh((4, 1), ("data", "model"), devices=topo.devices)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("block_rows", [8, 32])
def test_kmeans_items_grid_compiles(one_chip, block_rows):
    text = _compiled_text(
        partial(kmeans_assign_moments_batched, interpret=False,
                block_rows=block_rows), one_chip,
        ((ITEMS, ITEM), jnp.float32), ((ITEMS, K), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", [count_above_batched, mask_apply_batched],
                         ids=["count", "mask"])
def test_topk_items_grid_compiles(one_chip, kernel):
    text = _compiled_text(
        partial(kernel, interpret=False), one_chip,
        ((ITEMS, ITEM), jnp.float32), ((ITEMS,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k, n", [(D_MODEL, 2 * D_UP), (D_UP, D_MODEL)],
                         ids=["up", "down"])
def test_packed_4bit_gemm_compiles(one_chip, k, n):
    k2 = -(-k // 2 // 256) * 256   # packed rows, padded to the K tile
    text = _compiled_text(
        partial(quant_matmul_packed, interpret=False), one_chip,
        ((SLOTS, k2), jnp.float32), ((SLOTS, k2), jnp.float32),
        ((k2, n), jnp.uint8), ((K,), jnp.float32))
    assert "tpu_custom_call" in text


def test_singleton_kernel_cstep_compiles_on_mesh(mesh4, monkeypatch):
    """Singleton groups under a 4-chip mesh: GSPMD cannot partition a
    Pallas call, so the grouped C step must run their kernels in a
    manual region."""
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    params = {"a": jax.ShapeDtypeStruct((D_MODEL, D_UP), jnp.float32),
              "s": jax.ShapeDtypeStruct((2, D_MODEL, D_UP), jnp.float32)}
    tasks = [CompressionTask("prune", r"^a$", AsVector(),
                             ConstraintL0Pruning(kappa=ITEM // 10)),
             CompressionTask("quant", r"^s$", AsStacked("vector"),
                             AdaptiveQuantization(k=K, iters=2))]
    lc = LCAlgorithm(tasks, [1e-2], cstep_backend="pallas", mesh=mesh4)
    state = jax.eval_shape(lc.init, params)
    rep = NamedSharding(mesh4, P())
    put = partial(jax.tree_util.tree_map,
                  lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                 sharding=rep))
    text = lc._c_step.lower(put(params), put(state)).compile().as_text()
    assert "tpu_custom_call" in text
