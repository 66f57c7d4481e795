"""The readers of the trainer's own spans and counters, checked on
hand-made history records whose answers are known.

Run from the root of the checkout:

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests
"""
from __future__ import annotations

import pytest

import tiny  # noqa: F401  (puts the benchmark's modules on the path)
from harness import HERE
from refs import load_module

NAMES = ("step_host_ms", "data_wait_ms", "starved_step_share",
         "l_step_drain_ms", "lc_boundary_ms")


def reader(name):
    return load_module(HERE / "metrics" / f"{name}.py", "metric_" + name).read


def record(step_ms, data_ms, steps, starved, drain, c_step, l_step, it):
    return {"c_step_ms": c_step,
            "host_ms": {"lc.iteration": it, "lc.l_step": l_step,
                        "lc.step": step_ms, "lc.step.data": data_ms,
                        "lc.drain": drain, "lc.c_step": c_step},
            "counts": {"lc.iteration": 1, "lc.l_step": 1, "lc.step": steps,
                       "lc.step.data": steps, "lc.step.starved": starved,
                       "lc.drain": 1, "lc.c_step": 1}}


HISTORY = [record(step_ms=800.0, data_ms=4.0, steps=10, starved=1,
                  drain=100.0, c_step=1000.0, l_step=810.0, it=1950.0),
           record(step_ms=600.0, data_ms=6.0, steps=10, starved=3,
                  drain=300.0, c_step=1200.0, l_step=610.0, it=2190.0)]

EXPECTED = {
    "step_host_ms": 1400.0 / 20,
    "data_wait_ms": 10.0 / 20,
    "starved_step_share": 100.0 * 4 / 20,
    "l_step_drain_ms": 200.0,
    # (1950 - 810 - 100 - 1000 + 2190 - 610 - 300 - 1200) / 2
    "lc_boundary_ms": 60.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_known_history(name):
    assert reader(name)({"history": HISTORY}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_without_the_spans(name):
    read = reader(name)
    assert read({}) is None
    assert read({"history": []}) is None
    # a program that records no spans (only c_step_ms, as before them)
    assert read({"history": [{"c_step_ms": 1.0}]}) is None
    # one record of the window lacks them
    assert read({"history": HISTORY + [{"c_step_ms": 1.0}]}) is None


def test_starved_share_without_the_counter_is_none():
    hist = [dict(r, counts={k: v for k, v in r["counts"].items()
                            if k != "lc.step.starved"}) for r in HISTORY]
    assert reader("starved_step_share")({"history": hist}) is None
    assert reader("step_host_ms")({"history": hist}) == \
        pytest.approx(EXPECTED["step_host_ms"])
