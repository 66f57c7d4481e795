"""Host spans and counters of the training loop.

``Spans.span(name)`` times a block of host code twice over: it enters a
``jax.profiler.TraceAnnotation`` of that name, so that a profile shows
the block on the host track, on the clock of the device's events, and it
adds the block's ``perf_counter_ns`` duration and one count to the
recorder's totals for the name. ``Spans.count(name, n)`` bumps a
counter. ``Spans.take()`` hands the totals over and starts new ones.

The recorder is always on. Off a profile a span costs two clock reads,
two dict updates and a TraceMe that records nothing; it changes no
traced program.
"""
from __future__ import annotations

import time
from collections import defaultdict

import jax


class _Span:
    __slots__ = ("spans", "name", "ann", "t0")

    def __init__(self, spans: "Spans", name: str, step: int | None):
        self.spans, self.name = spans, name
        self.ann = (jax.profiler.TraceAnnotation(name) if step is None else
                    jax.profiler.StepTraceAnnotation(name, step_num=step))

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter_ns() - self.t0) * 1e-6
        self.ann.__exit__(*exc)
        self.spans._add(self.name, ms)
        return False


class Spans:
    """Per-name host milliseconds and counts since the last ``take()``."""

    def __init__(self):
        self._ms: defaultdict[str, float] = defaultdict(float)
        self._counts: defaultdict[str, int] = defaultdict(int)
        #: the duration of each name's latest span
        self.last_ms: dict[str, float] = {}

    def span(self, name: str, step: int | None = None) -> _Span:
        """A context manager timing its block under ``name``; with
        ``step`` it is a ``StepTraceAnnotation``, so a profiler's step
        view splits at it."""
        return _Span(self, name, step)

    def _add(self, name: str, ms: float) -> None:
        self._ms[name] += ms
        self._counts[name] += 1
        self.last_ms[name] = ms

    def count(self, name: str, n: int = 1) -> None:
        self._counts[name] += n

    def take(self) -> dict:
        """``{"host_ms": {name: ms}, "counts": {name: n}}`` since the last
        call; the totals start again from zero."""
        out = {"host_ms": dict(self._ms), "counts": dict(self._counts)}
        self._ms.clear()
        self._counts.clear()
        return out
