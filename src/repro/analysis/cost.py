"""Roofline cost model + group planner core for the C-step engine.

This module holds the machinery behind the cost-model-driven group
planner (`core/grouping.py` wires it in at ``build_groups``/
``grouped_compress`` time):

* :class:`HardwareSpec` — published peak FLOPs / HBM / interconnect /
  VMEM constants per device kind, looked up by the exact
  ``device_kind`` of ``jax.devices()[0]``.
* :class:`GroupPlan` — the per-group decision record: dispatch backend,
  Pallas items-grid tile rows, chunk count, shard mode, and the modeled
  roofline terms that justified them.
* ``plan_group(...)`` — the planner: an analytic first pass (per-solver
  FLOP/byte factors over the packed abstract shapes) optionally refined
  by lowering the chosen program once and running
  ``analysis/hlo_stats.analyze_hlo`` over the HLO text.
* The **plan cache** and **executable cache** — keyed by the group
  signature ``(scheme batch_key, item shape/dtype, n_items, operand
  treedef, mesh fingerprint, backend, hardware)`` so repeated LC
  boundaries pay zero re-lower/re-trace.  ``cache_stats()`` exposes
  hit/miss counters; ``lint/trace_count.check_planner_cache`` and
  ``benchmarks/bench_roofline.py`` assert the miss count stays flat
  across boundaries.

The module deliberately does NOT import ``core.grouping`` (grouping
imports us); lowering callables are passed in by the caller.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import numpy as np


# ---------------------------------------------------------------------------
# Hardware specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Peak-rate constants for one device kind.

    ``kind`` is the exact ``device.device_kind`` string the device
    reports; :func:`detect_hardware` looks it up in :data:`HARDWARE`.
    """

    name: str
    kind: str
    peak_flops: float      # bf16 FLOP/s per chip
    hbm_bw: float          # bytes/s per chip
    link_bw: float         # interconnect bytes/s per chip (one direction)
    vmem_bytes: int        # fast on-chip memory per core
    hbm_bytes: int         # device memory per chip

    @property
    def ridge_intensity(self) -> float:
        """FLOP/byte above which a kernel is compute-bound."""
        return self.peak_flops / self.hbm_bw


# Published per-chip peaks, Google Cloud TPU documentation ("TPU v4",
# "TPU v5e", "TPU v5p", "TPU v6e" system-architecture pages): bf16
# FLOP/s, HBM bandwidth and HBM capacity. v5e: 197 TFLOP/s bf16,
# 819 GB/s, 16 GB. ``link_bw`` is one ICI link in one direction.
TPU_V4 = HardwareSpec("tpu-v4", "TPU v4", 275e12, 1228e9, 75e9,
                      16 * 2**20, 32 * 2**30)
TPU_V5E = HardwareSpec("tpu-v5e", "TPU v5 lite", 197e12, 819e9, 50e9,
                       16 * 2**20, 16 * 2**30)
TPU_V5P = HardwareSpec("tpu-v5p", "TPU v5", 459e12, 2765e9, 100e9,
                       16 * 2**20, 95 * 2**30)
TPU_V6E = HardwareSpec("tpu-v6e", "TPU v6 lite", 918e12, 1640e9, 100e9,
                       32 * 2**20, 32 * 2**30)
# Host CPU: coarse numbers (one server socket), used only to rank
# alternatives off-TPU; they predict no wall clock.
CPU = HardwareSpec("cpu", "cpu", 1e12, 100e9, 25e9,
                   32 * 2**20, 64 * 2**30)

#: exact ``device_kind`` → spec, for every TPU this repo has peaks for
HARDWARE = {spec.kind: spec for spec in (TPU_V4, TPU_V5E, TPU_V5P,
                                         TPU_V6E)}


def detect_hardware(devices=None) -> HardwareSpec:
    """Map ``jax.devices()`` onto a :class:`HardwareSpec`.

    A TPU is looked up by its exact ``device_kind``; a TPU kind missing
    from :data:`HARDWARE` raises rather than borrowing another chip's
    peaks. Any other platform gets :data:`CPU`.
    """
    if devices is None:
        devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        return CPU
    kind = devices[0].device_kind
    if kind not in HARDWARE:
        raise ValueError(
            f"no peaks for TPU device_kind {kind!r}; known kinds: "
            f"{sorted(HARDWARE)}")
    return HARDWARE[kind]


# ---------------------------------------------------------------------------
# The plan record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One group's planner decisions plus the cost terms behind them.

    ``source`` is ``"analytic"`` when only the closed-form estimate ran
    and ``"hlo"`` when the lowered program was analyzed; ``fallbacks``
    records every decision the planner wanted but could not apply (the
    Layer-3 lint flags plans whose fallbacks went unreported).
    """

    backend: str                    # actual dispatch backend ("jnp"/...)
    solver: str | None              # registry solver name (None = vmap)
    block_rows: int | None          # Pallas items-grid tile rows
    n_chunks: int                   # launches the packed group splits into
    shard_mode: str                 # "gspmd" | "shard_map" | "none"
    flops: float                    # modeled FLOPs for the whole group
    bytes: float                    # modeled HBM traffic (bytes)
    coll_bytes: float               # modeled collective traffic (bytes)
    t_compute: float                # seconds at peak_flops
    t_memory: float                 # seconds at hbm_bw
    t_collective: float             # seconds at link_bw
    working_set_bytes: int          # packed operands + outputs resident
    source: str                     # "analytic" | "hlo"
    fallbacks: tuple[str, ...]      # decisions not applied, with reasons
    hardware: str                   # HardwareSpec.name used

    @property
    def modeled_ms(self) -> float:
        return 1e3 * max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def bottleneck(self) -> str:
        t = {"compute": self.t_compute, "memory": self.t_memory,
             "collective": self.t_collective}
        return max(t, key=t.get)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["modeled_ms"] = self.modeled_ms
        d["bottleneck"] = self.bottleneck
        return d


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

_PLAN_CACHE: dict[tuple, GroupPlan] = {}
_EXEC_CACHE: dict[tuple, Any] = {}
_STATS = {"plan_hits": 0, "plan_misses": 0,
          "exec_hits": 0, "exec_misses": 0}


def cache_stats() -> dict:
    """Copy of the hit/miss counters (lint + bench assert on these)."""
    return dict(_STATS, plan_entries=len(_PLAN_CACHE),
                exec_entries=len(_EXEC_CACHE))


def clear_caches() -> None:
    _PLAN_CACHE.clear()
    _EXEC_CACHE.clear()
    for k in _STATS:
        _STATS[k] = 0


def _leaf_sig(x) -> tuple:
    return (tuple(getattr(x, "shape", ())),
            str(getattr(x, "dtype", type(x).__name__)))


def _mesh_fingerprint(mesh) -> tuple:
    if mesh is None:
        return ()
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def plan_key(signature, n_items, arrays, mesh, backend,
             hw: HardwareSpec | None = None) -> tuple:
    """Cache key for a group's plan/executable.

    ``signature`` is the group's ``group_signature`` tuple (scheme
    batch_key + item shape/dtype + view kind); ``arrays`` the packed
    operand pytree (abstract or concrete — only shapes/dtypes and the
    treedef are hashed).
    """
    hw = hw or detect_hardware()
    if signature is None:
        signature = ("ungrouped",)
    leaves, treedef = jax.tree_util.tree_flatten(arrays)
    return (tuple(signature), int(n_items),
            tuple(_leaf_sig(x) for x in leaves), str(treedef),
            _mesh_fingerprint(mesh), str(backend), hw.name)


# ---------------------------------------------------------------------------
# Analytic cost model
# ---------------------------------------------------------------------------

# Coarse FLOPs-per-input-element factors per registry solver. They only
# need to be the right order of magnitude: the planner compares a
# handful of discrete alternatives, and the HLO refinement pass
# replaces them with counted FLOPs where lowering is available.
def _solver_flop_factor(solver: str | None, signature) -> float:
    if solver == "kmeans_lloyd":
        # iters × (K distances + onehot moments) per element
        k = _sig_field(signature, "k", 4)
        iters = _sig_field(signature, "iters", 25)
        return 3.0 * float(k) * float(iters)
    if solver == "topk_mask":
        return 2.0 * 30.0            # bisection feasibility sweeps
    if solver in ("lowrank_rsvd", "rank_select"):
        # sketch + power iters + finisher ≈ (2·POWER+2)·k matmul passes
        k = _sig_field(signature, "max_rank", 16) + 16
        return 2.0 * 8.0 * float(k) / 8.0
    if solver in ("project_l1_ball", "soft_threshold"):
        return 10.0                  # sort-dominated / elementwise
    return 20.0                      # unknown solver / vmap fallback


def _sig_field(signature, name: str, default):
    """Best-effort scalar pull from a group signature tuple (they carry
    scheme batch_key entries like ``("quant-kmeans", 4, 25)``)."""
    flat = []

    def walk(x):
        if isinstance(x, tuple):
            for y in x:
                walk(y)
        else:
            flat.append(x)

    walk(tuple(signature))
    ints = [x for x in flat if isinstance(x, int) and not
            isinstance(x, bool)]
    if name == "k" and ints:
        return ints[0]
    if name == "iters" and len(ints) > 1:
        return ints[1]
    if name == "max_rank" and ints:
        return ints[-1]
    return default


def _tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(dtype).itemsize
    return total


def estimate_terms(signature, solver: str | None, arrays, out_shapes,
                   hw: HardwareSpec, mesh=None,
                   shard_items: bool = False) -> dict:
    """Closed-form roofline terms for one packed group.

    ``arrays`` / ``out_shapes`` are pytrees of (abstract) arrays; the
    model is per-chip when ``shard_items`` (item axis sharded over the
    mesh) else whole-group.
    """
    in_bytes = _tree_bytes(arrays)
    out_bytes = _tree_bytes(out_shapes)
    n_elems = max(1, in_bytes // 4)
    flops = _solver_flop_factor(solver, signature) * float(n_elems)
    total_bytes = float(in_bytes + out_bytes)
    chips = 1
    coll_bytes = 0.0
    if mesh is not None and mesh.devices.size > 1:
        chips = int(mesh.devices.size)
        if shard_items:
            flops /= chips
            total_bytes /= chips
        else:
            # replicated solve: every chip reads the full group and the
            # result is all-gathered conceptually — model the output
            # traffic as the collective term
            coll_bytes = float(out_bytes)
    return {
        "flops": flops,
        "bytes": total_bytes,
        "coll_bytes": coll_bytes,
        "t_compute": flops / hw.peak_flops,
        "t_memory": total_bytes / hw.hbm_bw,
        "t_collective": coll_bytes / hw.link_bw if coll_bytes else 0.0,
        "working_set_bytes": int(in_bytes + out_bytes),
        "chips": chips,
    }


def refine_with_hlo(hlo_text: str, terms: dict,
                    hw: HardwareSpec) -> dict:
    """Replace the analytic FLOP/byte counts with counted ones from the
    lowered HLO (``analysis/hlo_stats``). Collective bytes come from
    the same pass. Falls back to ``terms`` untouched on parse failure.
    """
    from repro.analysis import hlo_stats
    stats = hlo_stats.analyze_hlo(hlo_text)
    refined = dict(terms)
    if stats.flops > 0:
        refined["flops"] = float(stats.flops)
        refined["t_compute"] = stats.flops / hw.peak_flops
    if stats.bytes > 0:
        refined["bytes"] = float(stats.bytes)
        refined["t_memory"] = stats.bytes / hw.hbm_bw
    coll = float(stats.coll_bytes)
    refined["coll_bytes"] = coll
    refined["t_collective"] = coll / hw.link_bw if coll else 0.0
    return refined


# ---------------------------------------------------------------------------
# Decision helpers
# ---------------------------------------------------------------------------

#: planner-tunable tile-row candidates for the items-grid kernels
BLOCK_ROWS_CANDIDATES = (8, 16, 32)

#: below this working set the Pallas launch overhead dominates — stay
#: on the fused jnp path even on TPU
_MIN_PALLAS_BYTES = 1 << 20

#: test hook — force the chunk budget down so small groups split.
#: ``None`` means "derive from the hardware spec".
CHUNK_BUDGET_BYTES: int | None = None


def chunk_budget(hw: HardwareSpec) -> int:
    if CHUNK_BUDGET_BYTES is not None:
        return int(CHUNK_BUDGET_BYTES)
    # a packed group should leave headroom next to the train state:
    # cap its working set at 1/4 of device memory
    return hw.hbm_bytes // 4


def choose_backend(requested: str, solver: str | None,
                   registered: tuple[str, ...], terms: dict,
                   hw: HardwareSpec) -> tuple[str, list[str]]:
    """Pick the dispatch backend for a group.

    Explicit requests ("jnp"/"interpret"/"pallas") are honored — the
    planner only decides for ``"auto"``. Returns (backend, fallbacks).
    """
    fallbacks: list[str] = []
    if requested != "auto":
        return requested, fallbacks
    on_tpu = hw.name.startswith("tpu")
    if not on_tpu:
        return "jnp", fallbacks
    if "pallas" not in registered:
        if solver is not None:
            fallbacks.append(
                f"backend:pallas-unregistered-for-{solver}->jnp")
        return "jnp", fallbacks
    # memory-bound groups with a real working set win from the fused
    # items-grid kernels; tiny or compute-bound ones stay on XLA where
    # fusion already covers them
    intensity = terms["flops"] / max(terms["bytes"], 1.0)
    if terms["working_set_bytes"] >= _MIN_PALLAS_BYTES and \
            intensity < hw.ridge_intensity:
        return "pallas", fallbacks
    fallbacks.append("backend:pallas-skipped-small-or-compute-bound")
    return "jnp", fallbacks


def choose_block_rows(solver: str | None, backend: str, n_items: int,
                      item_elems: int, extra_vmem_per_row: int,
                      hw: HardwareSpec) -> tuple[int | None, list[str]]:
    """Tile rows for the items-grid Pallas kernels.

    Larger tiles amortize grid overhead; the pick is the largest
    candidate whose per-tile VMEM footprint fits in a quarter of VMEM
    and whose padding waste stays under 1/8 of the item. Off-TPU the
    kernels only ever run emulated (interpret mode), so the default
    tile is kept — tile changes reorder float accumulation, and the
    planner-on/planner-off bit-parity contract must hold on CPU.
    """
    from repro.kernels import dispatch as _dispatch
    if backend not in ("pallas", "interpret") or \
            solver not in _dispatch.TILED_SOLVERS:
        return None, []
    if not hw.name.startswith("tpu"):
        return None, []
    lanes = 128
    best = 8
    for rows in BLOCK_ROWS_CANDIDATES:
        tile_elems = rows * lanes
        vmem = tile_elems * 4 * 3 + rows * extra_vmem_per_row
        pad = (-item_elems) % tile_elems
        if vmem > hw.vmem_bytes // 4:
            continue
        if pad > max(item_elems, 1) / 8:
            continue
        best = rows
    return best, []


def choose_chunks(working_set_bytes: int, n_items: int,
                  hw: HardwareSpec) -> int:
    """Launch count for a packed group: split when the working set
    exceeds the chunk budget, never beyond one item per launch."""
    budget = max(1, chunk_budget(hw))
    n = -(-working_set_bytes // budget)      # ceil div
    return max(1, min(int(n), max(1, int(n_items))))


# ---------------------------------------------------------------------------
# The planner entry point
# ---------------------------------------------------------------------------

def plan_group(signature, n_items, arrays, out_shapes, *,
               requested_backend: str, solver: str | None,
               registered: tuple[str, ...] = (),
               gspmd_safe: bool = False, mesh=None,
               item_elems: int = 0, extra_vmem_per_row: int = 0,
               lower_fn: Callable[[str], str] | None = None,
               base_fallbacks: tuple = (),
               hw: HardwareSpec | None = None) -> GroupPlan:
    """Plan one packed group. Cached on :func:`plan_key`.

    ``lower_fn`` (optional) takes the *chosen* backend and returns the
    HLO text of the program that would run on it; when provided and
    parseable the analytic terms are replaced by counted ones
    (``source="hlo"``). ``registered`` lists the dispatch backends
    actually carrying ``solver``. ``base_fallbacks`` pre-records
    caller-side decisions (e.g. refinement deliberately skipped) so an
    analytic plan is never silent about why.
    """
    hw = hw or detect_hardware()
    key = plan_key(signature, n_items, arrays, mesh,
                   requested_backend, hw)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _STATS["plan_hits"] += 1
        return cached
    _STATS["plan_misses"] += 1

    shard_mode = "none"
    if mesh is not None and mesh.devices.size > 1:
        shard_mode = "gspmd" if (solver is not None and gspmd_safe) \
            else "shard_map"
    terms = estimate_terms(signature, solver, arrays, out_shapes, hw,
                           mesh=mesh, shard_items=shard_mode != "none")
    backend, fallbacks = choose_backend(requested_backend, solver,
                                        registered, terms, hw)
    fallbacks = list(base_fallbacks) + fallbacks
    block_rows, tile_fb = choose_block_rows(
        solver, backend, n_items, item_elems, extra_vmem_per_row, hw)
    fallbacks += tile_fb
    n_chunks = choose_chunks(terms["working_set_bytes"], n_items, hw)
    if n_chunks > 1 and shard_mode != "none":
        fallbacks.append("chunking-disabled-under-mesh")
        n_chunks = 1

    source = "analytic"
    if lower_fn is not None:
        try:
            hlo_text = lower_fn(backend)
            if hlo_text:
                terms = refine_with_hlo(hlo_text, terms, hw)
                source = "hlo"
        except Exception as e:  # lowering is best-effort refinement
            fallbacks.append(f"hlo-refine-failed:{type(e).__name__}")

    plan = GroupPlan(
        backend=backend, solver=solver, block_rows=block_rows,
        n_chunks=n_chunks, shard_mode=shard_mode,
        flops=terms["flops"], bytes=terms["bytes"],
        coll_bytes=terms["coll_bytes"], t_compute=terms["t_compute"],
        t_memory=terms["t_memory"], t_collective=terms["t_collective"],
        working_set_bytes=terms["working_set_bytes"], source=source,
        fallbacks=tuple(fallbacks), hardware=hw.name)
    _PLAN_CACHE[key] = plan
    return plan


# ---------------------------------------------------------------------------
# AOT executable cache
# ---------------------------------------------------------------------------

def get_executable(key: tuple, build: Callable[[], Any]):
    """Fetch (or compile-and-insert) an AOT executable for ``key``.

    ``build`` runs ``jax.jit(...).lower(...).compile()`` — exactly once
    per key; repeated LC boundaries (and even ``_build_steps()``
    rebuilds) hit the cache and pay zero re-lower/re-trace.
    """
    exe = _EXEC_CACHE.get(key)
    if exe is not None:
        _STATS["exec_hits"] += 1
        return exe
    _STATS["exec_misses"] += 1
    exe = build()
    _EXEC_CACHE[key] = exe
    return exe


# ---------------------------------------------------------------------------
# Serving-side tile chooser (quant_matmul)
# ---------------------------------------------------------------------------

def gemm_tiles(m: int, n: int, k: int, *, packed: bool = False,
               hw: HardwareSpec | None = None) -> dict:
    """Tile hints for the compressed-serving matmul kernels.

    Returns ``{"block_m", "block_n", "block_k"}`` sized so the three
    operand tiles fit a quarter of VMEM; callers clamp to their grid.
    """
    hw = hw or detect_hardware()
    budget = hw.vmem_bytes // 4
    bm, bn, bk = 128, 128, 128
    itemsize = 0.5 if packed else 4.0

    def fits(bm, bn, bk):
        return (bm * bk * 4 + bk * bn * itemsize + bm * bn * 4) <= budget

    for cand in (256, 512):
        if cand <= n and fits(bm, cand, bk):
            bn = cand
    for cand in (256, 512):
        if cand <= k and fits(bm, bn, cand):
            bk = cand
    return {"block_m": min(bm, max(8, m)),
            "block_n": min(bn, max(128, n)),
            "block_k": min(bk, max(128, k))}
