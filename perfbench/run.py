#!/usr/bin/env python3
"""On-chip benchmark of the LC compression job.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (at the root of the checkout) on the
accelerator this process finds, and prints one JSON object as the last
line of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (read from a profiler trace of part
of the window). ``checks`` holds each number that decides ``correct``
beside its limit; the same lines end standard error.

Everything a cell needs is found by name: the configuration in
``configs/<config>.json`` (its plain reference beside it), the traffic in
``traffic/<traffic>.json``, the limits of its comparison in
``limits/<workload>.json`` and each per-layer metric's reader in
``metrics/<metric>.py``. A cell of a new model or traffic mix of an
existing kind needs only new files there and entries in
``BENCHMARK.json``: its own in ``workloads`` (and ``configs``), and its
name appended to the ``workloads`` list of each metric it reports
(``lc_tokens_per_s`` and the per-layer metrics that apply to it); it
changes nothing else in an entry that is there.

A configuration file is one JSON object. Each key that names a field of
the program's ``ModelConfig`` (``src/repro/configs/base.py``) is passed
on: ``name``, the widths and counts (``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``,
``pattern_reps``, ...), ``pattern``, ``lead`` and ``tail`` as lists of
``[mixer, ffn, window]``, and the nested blocks ``moe``, ``mla``,
``mamba`` and ``xlstm`` as objects of their dataclass's fields, where
``null`` stays ``None`` (``"q_lora_rank": null``). ``subquadratic``, when
left out, is true where no layer's mixer is ``attn`` or ``mla``. The
descriptive keys ``source``, ``deployment``, ``reference`` (the
``.ref.py`` beside it), ``published``, ``reduced``, ``why_reduced``,
``assumed`` and ``sliding_window`` are not passed on; any other key is
an error (``harness.model_config``).

The plain reference ``configs/<reference>`` (``reference`` names it, as
``<config>.ref.py``) imports nothing of the program and defines, for a
configuration file ``c`` (a dict) and ``mm`` (``refs.MatMul``, through
which every matrix product goes):

* ``init_params(key, c)``: the weights, drawn from ``key`` as the
  program draws them, as nested dicts with the program's paths;
* ``logits_row(p, tokens, c, mm)``: the logits (S, vocab) of one row of
  tokens (S,);
* ``matmul_params(c)``: the weights a token meets in matrix products;
* ``mixer_flops_per_token(c, seq)``: the mixer's operations per token
  beyond its weight products, at rows of ``seq`` tokens;
* optionally ``aux_loss(p, tokens, c, mm)``: a term over the whole
  batch ``tokens`` (B, S), already weighted, that the model adds to its
  mean next-token loss, such as a router's balance term (its weight from
  ``c``, never the program's default; ``refs.switch_balance`` is the
  Switch term of one layer).

A configuration with ``moe`` is held to more rules at load: its ``moe``
states ``router_aux_weight``, a weight of 0 needs an ``assumed`` entry
``router_aux_weight`` that says why, its ``.ref.py`` defines
``aux_loss``, and the limits of each of its cells give ``aux_gap``: the
largest relative gap of a step's unweighted balance term, the program's
``metrics["aux"]`` against the reference's (``lcjob.py``'s docstring has
every number ``correct`` compares).

A traffic file of kind ``lc`` lists its compression ``tasks``
(``lcjob.resolve_tasks``). Each has a ``scheme``, a ``pattern`` (a
regular expression on parameter paths) and ``per_leaf`` (one task per
matching leaf). A ``kmeans`` task is ``per_leaf`` and gives ``k``,
``iters`` and optionally ``stack_ndim``: the number of leading axes of
each leaf that index separate items, each with its own codebook (0, the
default: the whole leaf is one item; 2 on a scanned expert leaf ``(L, E,
d, f)``: one per layer and expert). A ``topk`` task gives
``kappa_divisor`` and keeps that share of all its leaves as one vector.
A task the program cannot be given is an error at load.

Exits non-zero and prints no result when JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# the persistent compilation cache lives at one fixed path inside the
# checkout, so only a checkout's first run of a cell compiles
CACHE_DIR = ROOT / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def load_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its files loaded."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    cell["config_file"] = json.loads(
        (HERE / "configs" / f"{cell['config']}.json").read_text())
    cell["traffic_file"] = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads(
        (HERE / "limits" / f"{name}.json").read_text())
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import harness
    device = harness.accelerator(cell["chips"])
    if device is None:
        return 3
    harness.enable_cache(CACHE_DIR)
    job = harness.job(cell)
    result = job.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START,
                     device=device)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
