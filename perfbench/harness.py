"""Pieces every kind of cell shares: the device, the compile cache, the
configuration, seeds, spans, the profiler, per-layer readers, the result."""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

import jax

HERE = Path(__file__).resolve().parent
PEAKS = json.loads((HERE / "peaks.json").read_text())


# ----------------------------------------------------------------------
# device
# ----------------------------------------------------------------------
def accelerator(chips: int):
    """``{"platform", "kind", "count"}`` of the TPU this process sees, or
    None (after a message on stderr) when it sees none or too few."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"perfbench: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"perfbench: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind`` (``device_kind``)."""
    if kind not in PEAKS["devices"]:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS['devices'])}")
    return PEAKS["devices"][kind]


def memory_peak_bytes() -> int | None:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    vals = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(vals) if vals else None


def enable_cache(path: Path) -> None:
    jax.config.update("jax_compilation_cache_dir", str(path))
    # every program is cached, small ones too, so a second run compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache) while
    ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.active and event == self.EVENT:
            self.count += 1


# ----------------------------------------------------------------------
# configuration and seeds
# ----------------------------------------------------------------------
def model_config(c: dict):
    """The program's ``ModelConfig`` built from a configuration file."""
    from repro.configs.base import LayerSpec, ModelConfig, XLSTMCfg
    kw = {k: c[k] for k in (
        "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab_size", "pattern_reps", "rope_theta", "norm_eps",
        "tie_embeddings", "dtype", "remat", "attn_chunk_q",
        "attn_chunk_kv") if k in c}
    kw["pattern"] = tuple(LayerSpec(m, f, window=w) for m, f, w in c["pattern"])
    if "xlstm" in c:
        kw["xlstm"] = XLSTMCfg(**c["xlstm"])
    kw["subquadratic"] = all(m != "attn" for m, _, _ in c["pattern"])
    return ModelConfig(name=c["name"], **kw)


def job(cell: dict):
    """The module that runs cells of the traffic's kind: ``<kind>job.py``
    beside this file (``lcjob.py`` for ``"kind": "lc"``)."""
    import importlib
    kind = cell["traffic_file"]["kind"]
    if not (HERE / f"{kind}job.py").exists():
        raise SystemExit(f"unknown traffic kind {kind!r}")
    return importlib.import_module(f"{kind}job")


def seed_key(seed: int, salt: int = 0):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, salt)


# ----------------------------------------------------------------------
# spans and the profiler
# ----------------------------------------------------------------------
def wrap(obj, attr: str, span: str):
    """Put a ``bench.<span>`` host annotation around ``obj.attr``."""
    fn = getattr(obj, attr)

    def annotated(*a, **kw):
        with jax.profiler.TraceAnnotation("bench." + span):
            return fn(*a, **kw)
    setattr(obj, attr, annotated)


class Profiler:
    """Records one window with ``jax.profiler`` into a temporary directory
    and reduces it; the directory is deleted once read."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self._ann = None

    def start(self):
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self):
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self):
        import tracing
        try:
            return tracing.Trace(tracing.load_dir(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def per_layer(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its reader
    ``metrics/<name>.py``; a reader that finds nothing returns None and
    the metric is left out."""
    from refs import load_module
    out = {}
    for m in cell["per_layer"]:
        mod = load_module(HERE / "metrics" / f"{m['name']}.py",
                          "metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ----------------------------------------------------------------------
# result
# ----------------------------------------------------------------------
def checks_entry(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit)}


def emit(result: dict) -> None:
    """Checks as the last lines of stderr, the result as the last line of
    stdout, with ``checks`` as its last key."""
    checks = result.pop("checks")
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def free_device() -> None:
    """Drop every live device buffer this process still holds."""
    import gc
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()


def tree_to_host(tree):
    import numpy as np
    return jax.tree_util.tree_map(lambda a: np.asarray(a, dtype=np.float32),
                                  jax.device_get(tree))
