"""Reduction of a profiler trace to device times.

A run with ``--trace 1`` records a part of its window with
``jax.profiler`` and reads the ``.xplane.pb`` back with
``jax.profiler.ProfileData``. The reduction works on a flat list of
events ``(plane, line, name, start_ns, end_ns[, scope])``, so that a
test can check it on a small recorded list:

* device planes are those named ``/device:<platform>:<n>``; on each, the
  line ``XLA Ops`` holds one event per executed operation and
  ``XLA Modules`` one event per executed program;
* an operation's event is named by its HLO text; it is kept as the
  instruction's name (``while.929``, ``quant_matmul_packed.12``), and
  operations nest (a loop's event covers its body's);
* an operation's scope is the ``op_name`` of its HLO instruction's
  metadata, the path of named scopes and transformations it was traced
  under (``jit(train_step)/jvp(...)/moe_ffn/dot_general``). A TPU
  profile does not carry it (an op event holds the instruction's text
  without its metadata, and no stat but its offset and duration), so
  ``Trace.add_hlo`` fills it in from each program's compiled HLO text;
  ``""`` where none is known;
* host spans are the harness's own ``jax.profiler.TraceAnnotation``s,
  named ``bench.<what>``, on the host plane;
* the traced window is the host span ``bench.window``; device events are
  clipped to it.

Busy time is the union of the operation intervals on a device, averaged
over the devices; the idle share is one less busy over the window.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
# the op_name of an instruction's metadata
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# one instruction of HLO text: its name and the rest of its line
HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)


def events_from_profile(pd) -> list[tuple]:
    """Flatten a ``jax.profiler.ProfileData`` into event tuples, keeping
    the device planes' op and module lines and the harness's host spans."""
    out = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device and line.name == OPS_LINE:
                    name = name.split(" = ", 1)[0].lstrip("%")
                elif not device and not name.startswith(SPAN_PREFIX):
                    continue
                start = float(ev.start_ns)
                out.append((plane.name, line.name, name, start,
                            start + float(ev.duration_ns)))
    return out


def hlo_scopes(text: str) -> dict[str, str]:
    """``{instruction name: op_name}`` of one program's HLO text
    (``Compiled.as_text()``), for the instructions that carry one."""
    out = {}
    for name, rest in HLO_LINE.findall(text):
        m = OP_NAME.search(rest)
        if m:
            out[name] = m.group(1)
    return out


def program_name(module_event: str) -> str:
    """The program of an ``XLA Modules`` event, as ``top_ops`` prints it:
    ``jit_train_step(12)`` is ``train_step``."""
    return re.sub(r"^jit_|\(\d+\)$", "", module_event)


def load_dir(trace_dir: str) -> list[tuple]:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return events_from_profile(ProfileData.from_file(files[-1]))


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Trace:
    """Device times of one traced window."""

    def __init__(self, events: list[tuple]):
        events = [tuple(ev[:5]) + ((ev[5] if len(ev) > 5 else ""),)
                  for ev in events]
        wins = [(s, e) for p, l, n, s, e, _ in events
                if not DEVICE_PLANE.match(p) and n == WINDOW_SPAN]
        if not wins:
            raise RuntimeError(f"trace holds no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = wins[-1]
        self.spans = [(n, s, e) for p, l, n, s, e, _ in events
                      if not DEVICE_PLANE.match(p) and n != WINDOW_SPAN
                      and e > self.t0 and s < self.t1]
        self.devices = sorted({p for p, *_ in events
                               if DEVICE_PLANE.match(p)})
        clip = []
        for p, l, n, s, e, sc in events:
            if DEVICE_PLANE.match(p) and e > self.t0 and s < self.t1:
                clip.append((p, l, n, max(s, self.t0), min(e, self.t1), sc))
        self.events = clip

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _ops(self, device=None):
        return [(n, s, e) for p, l, n, s, e, _ in self.events
                if l == OPS_LINE and (device is None or p == device)]

    def _programs(self, device):
        """``(start, end, program)`` of the device's ``XLA Modules``
        events, in order of start."""
        return sorted((s, e, program_name(n))
                      for p, l, n, s, e, _ in self.events
                      if l == MODULES_LINE and p == device)

    def busy_intervals(self, device):
        return _union([(s, e) for _, s, e in self._ops(device)])

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices
                  for s, e in self.busy_intervals(d))
        return tot / len(self.devices) * 1e-9

    def modules(self, pattern: str, device=None) -> tuple[int, float]:
        """(calls, device seconds) of the programs whose name matches."""
        device = device or (self.devices[0] if self.devices else None)
        rx = re.compile(pattern)
        hits = [e - s for p, l, n, s, e, _ in self.events
                if l == MODULES_LINE and p == device and rx.search(n)]
        return len(hits), sum(hits) * 1e-9

    def ops(self, pattern: str, device=None) -> tuple[int, float]:
        """(executions, device seconds) of the operations whose name
        matches."""
        device = device or (self.devices[0] if self.devices else None)
        rx = re.compile(pattern)
        hits = [e - s for n, s, e in self._ops(device) if rx.search(n)]
        return len(hits), sum(hits) * 1e-9

    def top_ops(self, n: int = 10) -> list[list]:
        """The outermost operations that took most device time, each
        named ``<program>/<instruction>``; an operation inside another
        (a loop's body) counts in its parent only."""
        if not self.devices:
            return []
        dev = self.devices[0]
        mods = self._programs(dev)
        agg: dict[str, float] = defaultdict(float)
        end, mi = -1.0, 0
        for name, s, e in sorted(self._ops(dev), key=lambda x: (x[1], -x[2])):
            if e <= end:
                continue                   # inside an op already counted
            end = e
            while mi + 1 < len(mods) and mods[mi + 1][0] <= s:
                mi += 1
            prog = mods[mi][2] if mods and mods[mi][0] <= s < mods[mi][1] \
                else "?"
            agg[f"{prog}/{name}"] += (e - s) * 1e-9
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds of the first device inside the window, summed by
        the innermost harness span the gap's midpoint falls in."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        agg: dict[str, float] = defaultdict(float)
        for s, e in gaps:
            mid = (s + e) / 2
            inner = [(ss, nn) for nn, ss, ee in self.spans if ss <= mid < ee]
            name = max(inner)[1] if inner else "outside any span"
            agg[name] += (e - s) * 1e-9
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}

    def scoped(self, pattern: str, device=None) -> tuple[int, float]:
        """(executions, device seconds) of the operations whose scope
        matches ``pattern``. The seconds are the union of their intervals,
        since ops nest (a loop's event covers its body's); an execution is
        a matching op that lies inside no other matching op."""
        device = device or (self.devices[0] if self.devices else None)
        rx = re.compile(pattern)
        hits = sorted((s, -e) for p, l, n, s, e, sc in self.events
                      if l == OPS_LINE and p == device and sc
                      and rx.search(sc))
        calls, end = 0, -1.0
        for _, neg_e in hits:
            if -neg_e > end:               # inside no op already counted
                calls += 1
                end = -neg_e
        busy = sum(e - s for s, e in _union([(s, -ne) for s, ne in hits]))
        return calls, busy * 1e-9

    def programs(self) -> set[str]:
        """The programs the window ran, as ``program_name`` gives them."""
        return {program_name(n) for p, l, n, *_ in self.events
                if l == MODULES_LINE}

    def add_hlo(self, texts) -> int:
        """Fill in the scope of each op event that has none from its
        program's compiled HLO text (``texts``: ``Compiled.as_text()`` of
        each program, found by its ``HloModule`` name). An op belongs to
        the program whose module event holds its start. An instruction
        with no ``op_name`` of its own (a copy the compiler put in) takes
        the scope of the op it runs inside, and at the top of its program
        ``jit(<program>)``. Returns the number of ops given a scope from
        their own instruction."""
        tables = {program_name(HLO_MODULE.search(t).group(1)): hlo_scopes(t)
                  for t in texts}
        new, own = {}, 0
        for d in self.devices:
            progs = self._programs(d)
            starts = [m[0] for m in progs]
            ops = sorted((s, -e, i) for i, (p, l, n, s, e, sc)
                         in enumerate(self.events) if p == d and l == OPS_LINE)
            stack = []                      # (end, scope) of enclosing ops
            for s, neg_e, i in ops:
                e, (_, _, n, _, _, sc) = -neg_e, self.events[i]
                while stack and stack[-1][0] <= s:
                    stack.pop()
                j = bisect.bisect_right(starts, s) - 1
                prog = progs[j][2] if j >= 0 and s < progs[j][1] else None
                if not sc and prog in tables:
                    sc = tables[prog].get(n, "")
                    own += bool(sc)
                    if not sc:
                        sc = stack[-1][1] if stack and e <= stack[-1][0] \
                            else f"jit({prog})"
                    new[i] = sc
                stack.append((e, sc))
        self.events = [ev[:5] + (new[i],) if i in new else ev
                       for i, ev in enumerate(self.events)]
        return own
