"""The trace reduction, checked on event lists whose answers are known.

Run from the root of the checkout:

    JAX_PLATFORMS=cpu python3 -m pytest -q perfbench/tests
"""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts the benchmark's modules on the path)
import tracing

DATA = Path(__file__).resolve().parent / "data"
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE


def ev(plane, line, name, start, end, *scope):
    return (plane, line, name, float(start), float(end), *scope)


def synthetic():
    return [
        ev(HOST, "python3", "bench.window", 1000, 11000),
        ev(HOST, "python3", "bench.c_step", 6000, 9000),
        ev(HOST, "python3", "bench.monitor", 9000, 10500),
        ev(HOST, "python3", "other", 0, 20000),           # not a bench span
        ev(D0, MODS, "jit_train_step(3)", 500, 5000),      # clipped to 1000
        ev(D0, OPS, "fusion.1", 500, 3000),
        ev(D0, OPS, "fusion.2", 2500, 5000),               # overlaps fusion.1
        ev(D0, MODS, "jit__c_step_impl(7)", 7000, 8000),
        ev(D0, OPS, "kmeans_kernel", 7000, 8000),
        ev(D0, OPS, "late", 12000, 13000),                 # outside the window
        ev(D1, OPS, "fusion.1", 1000, 2000),
    ]


def test_busy_is_the_union_clipped_and_averaged_over_devices():
    t = tracing.Trace(synthetic())
    assert t.window_s == pytest.approx(10000e-9)
    assert t.devices == [D0, D1]
    # device 0: [1000, 5000] and [7000, 8000] -> 5000 ns; device 1: 1000 ns
    assert t.busy_s() == pytest.approx((5000 + 1000) / 2 * 1e-9)


def test_modules_and_ops_by_name():
    t = tracing.Trace(synthetic())
    assert t.modules(r"train_step") == (1, pytest.approx(4000e-9))
    assert t.modules(r"_c_step_impl") == (1, pytest.approx(1000e-9))
    assert t.ops(r"kmeans") == (1, pytest.approx(1000e-9))
    assert t.ops(r"late") == (0, 0)


def test_idle_gaps_are_named_by_the_innermost_span():
    t = tracing.Trace(synthetic())
    gaps = dict(t.idle_gaps())
    # [5000, 7000] lies in no bench span before 6000 (midpoint 6000 is in
    # bench.c_step), [8000, 11000] has its midpoint 9500 in bench.monitor
    assert gaps == {"bench.c_step": pytest.approx(2000e-9),
                    "bench.monitor": pytest.approx(3000e-9)}
    top = dict(t.top_ops())
    assert top == {"train_step/fusion.1": pytest.approx(2000e-9),
                   "train_step/fusion.2": pytest.approx(2500e-9),
                   "_c_step_impl/kmeans_kernel": pytest.approx(1000e-9)}


def test_an_op_inside_another_counts_in_its_parent_only():
    evs = synthetic() + [ev(D0, OPS, "inner.3", 7200, 7600)]
    top = dict(tracing.Trace(evs).top_ops())
    assert "_c_step_impl/inner.3" not in top
    assert top["_c_step_impl/kmeans_kernel"] == pytest.approx(1000e-9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(RuntimeError):
        tracing.Trace([e for e in synthetic() if e[2] != "bench.window"])


def test_recorded_trace():
    """A slice of a trace recorded on a TPU v5e: the reduction's numbers
    against a plain sweep over the same events."""
    rec = json.loads(gzip.open(DATA / "trace_small.json.gz", "rt").read())
    events = [tuple(e) for e in rec["events"]]
    t = tracing.Trace(events)
    assert t.window_s == pytest.approx(rec["expect"]["window_s"])
    assert t.busy_s() == pytest.approx(rec["expect"]["busy_s"])
    assert t.top_ops() == [[k, pytest.approx(v)] for k, v in
                           rec["expect"]["top_ops"]]
    assert t.idle_gaps() == [[k, pytest.approx(v)] for k, v in
                             rec["expect"]["idle_gaps"]]
    calls, secs = t.modules(r"decode_impl")
    assert calls == 1 and secs == pytest.approx(rec["decode_impl"][1])
    assert 0 < t.busy_s() <= t.window_s
    # busy by a sweep over 1 ns cells of the first device's op events
    d0 = t.devices[0]
    ops = [(max(s, t.t0), min(e, t.t1)) for p, l, n, s, e in events
           if p == d0 and l == OPS and e > t.t0 and s < t.t1]
    edges = sorted({x for iv in ops for x in iv})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for s, e in ops))
    assert sum(e - s for s, e in t.busy_intervals(d0)) == pytest.approx(busy)


# ----------------------------------------------------------------------
# device time by scope
# ----------------------------------------------------------------------
REMAT = "jit(train_step)/transpose(jvp(blk))/rematted_computation"


def scoped_events():
    """A train step on two devices: a loop whose body runs two scoped
    ops, an op after it, and ops that stick out of the window at both
    ends; one op with no scope."""
    return [
        ev(HOST, "python3", "bench.window", 1000, 11000),
        ev(D0, MODS, "jit_train_step(3)", 500, 11500),
        ev(D0, OPS, "fusion.1", 500, 1200, "jit(train_step)/embed"),
        ev(D0, OPS, "while.5", 1500, 4500, REMAT + "/while"),
        ev(D0, OPS, "fusion.7", 1600, 2600, REMAT + "/dot_general"),
        ev(D0, OPS, "fusion.8", 2700, 4400, REMAT + "/dot_general"),
        ev(D0, OPS, "fusion.9", 4600, 5600, "jit(train_step)/add"),
        ev(D0, OPS, "copy.2", 5600, 5800),
        ev(D0, OPS, "fusion.3", 10500, 12000, "jit(train_step)/adamw"),
        ev(D1, MODS, "jit_train_step(3)", 1900, 3100),
        ev(D1, OPS, "while.5", 2000, 3000, REMAT + "/while"),
        ev(D1, OPS, "fusion.7", 2100, 2900, REMAT + "/dot_general"),
    ]


def test_scoped_counts_nested_time_once():
    t = tracing.Trace(scoped_events())
    # the loop's 3000 ns hold its body's 2700 ns: counted once, one call
    assert t.scoped(r"rematted_computation") == (1, pytest.approx(3000e-9))
    assert t.scoped(r"rematted_computation/dot_general") == \
        (2, pytest.approx(2700e-9))
    # clipped to the window: [1000, 1200] + 3000 + 1000 + [10500, 11000]
    assert t.scoped(r"^jit\(train_step\)") == (4, pytest.approx(4700e-9))
    assert t.scoped(r"rematted_computation", device=D1) == \
        (1, pytest.approx(1000e-9))
    assert t.scoped(r"no_such_scope") == (0, 0)
    # the ops by name are read as before: the loop's body counts again
    assert t.ops(r"^(while|fusion)\.[578]$") == (3, pytest.approx(5700e-9))


HLO = """HloModule jit_train_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%body.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, calls=%fc, \
metadata={op_name="jit(train_step)/transpose(jvp(blk))/rematted_computation/dot_general" \
source_file="blk.py" source_line=3}
}

ENTRY %main.9 (a.1: f32[4]) -> f32[4] {
  %a.1 = f32[4]{0} parameter(0)
  %while.5 = f32[4]{0} while(f32[4]{0} %a.1), condition=%c.2, body=%body.1, \
metadata={op_name="jit(train_step)/transpose(jvp(blk))/rematted_computation/while"}
  ROOT %copy.2 = f32[4]{0} copy(f32[4]{0} %while.5)
}
"""


def test_add_hlo_fills_scopes_from_the_program_text():
    evs = [e[:5] for e in scoped_events()]
    t = tracing.Trace(evs)
    assert t.scoped(r"rematted_computation") == (0, 0)
    # while.5 and fusion.7 on both devices by their own op_name
    assert t.add_hlo([HLO]) == 4
    assert t.scoped(r"rematted_computation") == (1, pytest.approx(3000e-9))
    assert t.scoped(r"rematted_computation/dot_general") == \
        (1, pytest.approx(1000e-9))
    # an op with none takes the scope of the op it runs in (fusion.8 the
    # loop's), and at the top of its program jit(train_step): copy.2 and
    # the ops the text does not hold, clipped to the window
    assert t.scoped(r"rematted_computation/while$") == \
        (1, pytest.approx(3000e-9))
    assert t.scoped(r"^jit\(train_step\)$") == (4, pytest.approx(1900e-9))


def test_recorded_trace_reads_as_before():
    """The recorded trace carries no scope: what ``ops``, ``top_ops`` and
    ``breakdown`` read is what they read before scopes were kept."""
    rec = json.loads(gzip.open(DATA / "trace_small.json.gz", "rt").read())
    events = [tuple(e) for e in rec["events"]]
    t = tracing.Trace(events)
    assert t.breakdown() == {"device_ops": rec["expect"]["top_ops"],
                             "idle_gaps": rec["expect"]["idle_gaps"]}
    d0 = t.devices[0]
    fus = [min(e, t.t1) - max(s, t.t0) for p, l, n, s, e in events
           if p == d0 and l == OPS and n.startswith("fusion")
           and e > t.t0 and s < t.t1]
    assert t.ops(r"^fusion") == (len(fus), sum(fus) * 1e-9)
    assert t.scoped(r".") == (0, 0)
