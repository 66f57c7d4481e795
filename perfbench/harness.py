"""Pieces every kind of cell shares: the device, the compile cache, the
configuration, seeds, spans, the profiler, per-layer readers, the result."""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import tempfile
import types
import typing
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
# keys of a configuration file that describe it and are not fields of
# ``ModelConfig``: the model's reference reads ``sliding_window``
DESCRIPTIVE = ("source", "deployment", "reference", "published", "reduced",
               "why_reduced", "assumed", "sliding_window")
# mixers whose cost grows with the square of the context
QUADRATIC = ("attn", "mla")


def _field_types(cls) -> dict:
    """``{field name: type}`` of a dataclass, its annotations resolved."""
    hints = typing.get_type_hints(cls)
    return {f.name: hints[f.name] for f in dataclasses.fields(cls)}


def _build(tp, v):
    """The JSON value ``v`` as a value of type ``tp``: a nested config
    dataclass from its dict (or from a list of its fields in order, as a
    ``LayerSpec`` from ``[mixer, ffn, window]``), a tuple from a list;
    ``None`` stays ``None``."""
    if v is None:
        return None
    origin, args = typing.get_origin(tp), typing.get_args(tp)
    if origin in (typing.Union, types.UnionType):
        (tp,) = [a for a in args if a is not type(None)]
        return _build(tp, v)
    if origin is tuple:
        return tuple(_build(args[0], x) for x in v)
    if dataclasses.is_dataclass(tp):
        if isinstance(v, list):
            return tp(*v)
        hints = _field_types(tp)
        return tp(**{k: _build(hints.get(k), x) for k, x in v.items()})
    return v


def model_config(c: dict):
    """The program's ``ModelConfig`` built from a configuration file.

    Every key that names a field of ``ModelConfig`` is passed on, nested
    blocks (``moe``, ``mla``, ``mamba``, ``xlstm``) built through their
    dataclass and ``pattern``, ``lead`` and ``tail`` as ``LayerSpec``s;
    the keys in ``DESCRIPTIVE`` are left out, and any other key is an
    error. ``subquadratic``, where the file does not give it, is true
    when no layer's mixer is in ``QUADRATIC``. A ``moe`` block states
    its ``router_aux_weight``, and a weight of 0 needs an ``assumed``
    entry of that name that says why."""
    from repro.configs.base import ModelConfig
    fields = _field_types(ModelConfig)
    unknown = sorted(set(c) - set(fields) - set(DESCRIPTIVE))
    if unknown:
        raise ValueError(
            f"configuration {c.get('name')!r}: {unknown} neither a field of "
            f"ModelConfig nor a descriptive key {DESCRIPTIVE}")
    if c.get("moe") is not None:
        _check_moe(c)
    kw = {k: _build(fields[k], v) for k, v in c.items() if k in fields}
    if "subquadratic" not in kw:
        specs = kw.get("lead", ()) + kw["pattern"] + kw.get("tail", ())
        kw["subquadratic"] = all(s.mixer not in QUADRATIC for s in specs)
    return ModelConfig(**kw)


def _check_moe(c: dict) -> None:
    """A file's ``moe`` block states the weight of the router's balance
    term, since the program's default is no published value; a weight of
    0, which leaves the term out of the loss, is explained in
    ``assumed``."""
    name = c.get("name")
    if "router_aux_weight" not in c["moe"]:
        raise ValueError(f"configuration {name!r}: moe does not state "
                         f"router_aux_weight")
    assumed = c.get("assumed")
    why = assumed.get("router_aux_weight") if isinstance(assumed, dict) \
        else None
    if c["moe"]["router_aux_weight"] == 0 and not why:
        raise ValueError(f"configuration {name!r}: router_aux_weight is 0 "
                         f"and no assumed entry router_aux_weight says why")


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


class Programs:
    """Keeps each program this process loads, compiled or read from the
    compile cache, from its making until ``close()``: a TPU profile names
    an op by its instruction alone, and the op's scope is in the metadata
    of the program's compiled HLO (``tracing.Trace.add_hlo``). The texts
    are printed only when asked for, after the window."""

    def __init__(self):
        from jax._src import compiler
        self._compiler = compiler
        self._load = compiler.compile_or_get_cached
        self.executables = []

        def keep(*a, **kw):
            exe = self._load(*a, **kw)
            self.executables.append(exe)
            return exe
        compiler.compile_or_get_cached = keep

    def texts(self, names) -> list[str]:
        """HLO text of each kept program whose name, as
        ``tracing.program_name`` gives it, is in ``names``."""
        import tracing
        return [m.to_string() for exe in self.executables
                for m in exe.hlo_modules()
                if tracing.program_name(m.name) in names]

    def close(self):
        self._compiler.compile_or_get_cached = self._load
        self.executables = []


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
