"""Host spans and counters of the training loop (``runtime/spans.py``).

* The recorder: nested spans add up per name, ``take()`` hands the
  totals over and starts again.
* ``LCTrainer`` in both modes: every history record holds the spans and
  counters of its own LC iteration.
* A profile of one LC iteration holds the same spans on the host plane,
  nested as the trainer opens them, with the recorder's counts and
  durations.
"""
import glob
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.runtime.spans import Spans
from test_trainer_overlap import KEY, _make_trainer

SPAN_NAMES = ("lc.iteration", "lc.l_step", "lc.step", "lc.step.data",
              "lc.drain", "lc.c_step")
COUNTER = "lc.step.starved"


# ----------------------------------------------------------------------
# the recorder
# ----------------------------------------------------------------------
def test_nested_spans_add_up_and_take_resets():
    sp = Spans()
    with sp.span("outer"):
        for i in range(3):
            with sp.span("inner", step=i):
                time.sleep(0.002)
    sp.count("hits")
    sp.count("hits", 2)
    sp.count("misses", 0)
    got = sp.take()
    assert got["counts"] == {"outer": 1, "inner": 3, "hits": 3, "misses": 0}
    assert got["host_ms"]["inner"] >= 6.0
    assert got["host_ms"]["outer"] >= got["host_ms"]["inner"]
    assert set(got["host_ms"]) == {"outer", "inner"}
    assert sp.last_ms["inner"] >= 2.0
    assert sp.take() == {"host_ms": {}, "counts": {}}


def test_span_is_recorded_when_its_block_raises():
    sp = Spans()
    with pytest.raises(RuntimeError):
        with sp.span("failing"):
            raise RuntimeError("boom")
    assert sp.take()["counts"] == {"failing": 1}


# ----------------------------------------------------------------------
# the trainer's records
# ----------------------------------------------------------------------
@pytest.mark.parametrize("overlap", ["off", "on"])
def test_each_record_holds_its_iterations_spans(overlap):
    trainer = _make_trainer(overlap=overlap, n_mu=2, steps_per_l=3)
    trainer.run(KEY)
    assert len(trainer.history) == 2
    for rec in trainer.history:
        ms, counts = rec["host_ms"], rec["counts"]
        assert set(ms) == set(SPAN_NAMES)
        assert set(counts) == set(SPAN_NAMES) | {COUNTER}
        assert counts["lc.step"] == counts["lc.step.data"] == 3
        for name in ("lc.iteration", "lc.l_step", "lc.drain", "lc.c_step"):
            assert counts[name] == 1, name
        assert 0 <= counts[COUNTER] <= counts["lc.step"]
        assert all(v >= 0.0 for v in ms.values())
        # nesting: the parts fit inside what holds them
        assert ms["lc.step.data"] <= ms["lc.step"] <= ms["lc.l_step"]
        assert (ms["lc.l_step"] + ms["lc.drain"] + ms["lc.c_step"]
                <= ms["lc.iteration"])
        if overlap == "off":
            assert rec["c_step_ms"] == ms["lc.c_step"]
    # the first step after init finds the device idle
    assert trainer.history[0]["counts"][COUNTER] >= 1


def test_straggler_monitor_sees_each_steps_host_time():
    trainer = _make_trainer(overlap="off", n_mu=1, steps_per_l=3)
    trainer.run(KEY)
    assert len(trainer.straggler.times) == 3
    assert trainer.straggler.times[-1] == trainer.spans.last_ms["lc.step"]


# ----------------------------------------------------------------------
# the same spans in a profile
# ----------------------------------------------------------------------
def _host_events(trace_dir):
    pd = ProfileData.from_file(
        glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1])
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for plane in pd.planes if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("lc.")]


def _inside(inner, outers):
    _, s, e = inner
    return any(os_ <= s and e <= oe for _, os_, oe in outers)


def test_profile_holds_the_recorders_spans(tmp_path):
    trainer = _make_trainer(overlap="off", n_mu=1, steps_per_l=3)
    state = trainer.init_state(KEY)
    jax.block_until_ready(state)
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer._run_serial(state, trainer.lc.mu_schedule, 0)
    finally:
        jax.profiler.stop_trace()
    rec = trainer.history[-1]

    events = _host_events(tmp_path)
    by_name = {n: [ev for ev in events if ev[0] == n] for n in SPAN_NAMES}
    for name in SPAN_NAMES:
        evs = by_name[name]
        assert len(evs) == rec["counts"][name], name
        trace_ms = sum(e - s for _, s, e in evs) * 1e-6
        ms = rec["host_ms"][name]
        assert abs(trace_ms - ms) <= max(0.05 * len(evs), 0.05 * ms), \
            (name, trace_ms, ms)

    parent = {"lc.l_step": "lc.iteration", "lc.drain": "lc.iteration",
              "lc.c_step": "lc.iteration", "lc.step": "lc.l_step",
              "lc.step.data": "lc.step"}
    for name, outer in parent.items():
        for ev in by_name[name]:
            assert _inside(ev, by_name[outer]), (name, outer)
    for ev in by_name["lc.drain"] + by_name["lc.c_step"]:
        assert not _inside(ev, by_name["lc.l_step"])
