"""The comparison that decides ``correct``, shown to fail.

Each test drives a whole run of a cell, cut to a size the CPU holds
(``tiny.py``), past the harness's look for a chip: set-up, window and
comparison, with the cell's own limits. A sound run must come out
correct; the control (the float32 reference computed in float8, put in
the program's place) and every planted fault must not. The program runs
in float32 here, so that a sound run reads round-off alone and what
separates the readings is the fault.

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests
"""
from __future__ import annotations

import json
import time

import pytest

import tiny

LC = ("phi3-mini-3.8b.lc-quant4", "phi3-mini-3.8b", "lc-quant4-b1s2048")
SEED = 2 ** 33 + 17          # wider than 32 bits, as a check's seeds are


def cell(spec, **kw):
    w, c, t = spec
    limits = json.loads((tiny.BENCH / "limits" / f"{w}.json").read_text())
    out = tiny.tiny_cell(w, c, t, limits, **kw)
    out["config_file"]["dtype"] = "float32"
    return out


# top-kappa over the attention matrices as one vector, k-means per
# matrix on the MLP: the traffic's other kind of task
TOPK = [{"scheme": "topk", "pattern": "stages/.*/(wq|wk|wv|wo)$",
         "kappa_divisor": 10},
        {"scheme": "kmeans", "pattern": "stages/.*/(w_gate|w_up|w_down)$",
         "per_leaf": True, "k": 16, "iters": 10}]


def lc_run(fault=None, traffic=None, **kw):
    import lcjob
    return lcjob.run(cell(LC, **(traffic or {})), seed=SEED, seconds=1.0,
                     trace=False, t_start=time.perf_counter(),
                     device=tiny.CPU, fault=fault, **kw)


@pytest.mark.parametrize("traffic", [None, {"tasks": TOPK}],
                         ids=["kmeans", "topk"])
def test_lc_sound_run_is_correct(traffic):
    res = lc_run(traffic=traffic)
    assert res["correct"], res["checks"]
    assert res["window_compiles"] == 0
    assert res["checks"]["cstep_excess"]["value"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "labels_altered", "cstep_unchanged",
                                   "cstep_altered"])
def test_lc_fault_is_not_correct(fault):
    res = lc_run(fault)
    assert not res["correct"], res["checks"]


def test_lc_control_is_not_correct():
    import harness
    import lcjob
    import refs
    c = cell(LC)
    ref = lcjob.reference_readings(c, SEED, refs.MatMul())
    harness.free_device()
    low = lcjob.reference_readings(c, SEED, refs.MatMul(lcjob.FP8))
    ok, checks = lcjob.judge(lcjob.gaps(low, ref), c["limits"])
    assert not ok, checks


def test_cstep_control_and_faults_read_above_the_limit():
    """The C step's control (the reference's k-means in float8 in the
    program's place) and its reference-side faults, read as calibration
    reads them."""
    res = lc_run(calibrate=True)
    lim = cell(LC)["limits"]["cstep_gap"]
    for r in ("control", "unchanged", "short"):
        assert res["numbers"][f"cstep_gap_{r}"] > lim, (r, res["numbers"])
