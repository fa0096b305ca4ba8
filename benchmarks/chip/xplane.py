"""From a profiler trace (``.xplane.pb``) to what the metrics read.

* The window is the host event ``bench.window`` (a
  ``jax.profiler.TraceAnnotation`` the harness holds open for exactly the
  measured window); everything is clipped to it.
* Device operations are the events of each TPU plane's ``XLA Ops`` line,
  named by their HLO instruction (the event's name is the instruction's
  text, ``%msgs_fused_packed.23 = f32[...] custom-call(...)``; the name is
  what precedes `` = ``). Busy time is the union of their intervals,
  averaged over the devices.
* MSDA sampling calls are the device operations whose name starts with
  ``msgs_`` (the Pallas kernels name themselves so, see
  ``MSDA_KERNELS``), grouped by the executable run (``XLA Modules``
  line) that holds them, for the runs that lie wholly in the window.
* Idle gaps are the stretches of the window in which no operation ran on
  device 0; each is put down to the harness span (``bench.*``) that was
  open on the host at its midpoint, or ``host.other`` where none was.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
from collections import defaultdict
from pathlib import Path

WINDOW = "bench.window"
HOST_PREFIX = "bench."
MSDA_KERNELS = re.compile(r"^msgs_")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HLO_NAME = re.compile(r"^%?([^\s=]+)")
MODULES_LINE = "XLA Modules"
TOP = 10


def load(path):
    """The ``ProfileData`` of an ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    """Device operations and harness spans of one traced window; times in
    nanoseconds on the trace's clock."""
    start: float
    end: float
    ops: dict            # device id -> [(start, end, name)], in the window
    spans: list          # [(start, end, name)] harness spans on the host
    modules: dict        # device id -> [(start, end, name)], wholly inside

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    def _busy(self, dev) -> list:
        return _union([(s, e) for s, e, _ in self.ops.get(dev, [])])

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.ops:
            return 0.0
        tot = [sum(e - s for s, e in self._busy(d)) for d in self.ops]
        return sum(tot) / len(tot) / 1e9

    def msda_calls(self, dev=None) -> list:
        """For each executable run wholly in the window, the device seconds
        of its MSDA sampling calls in time order (runs without one are
        left out)."""
        dev = min(self.ops) if dev is None else dev
        calls = [(s, e) for s, e, n in self.ops.get(dev, [])
                 if MSDA_KERNELS.match(n)]
        out = []
        for ms, me, _ in self.modules.get(dev, []):
            inner = [(e - s) / 1e9 for s, e in calls if ms <= s and e <= me]
            if inner:
                out.append(inner)
        return out

    def idle_gaps(self) -> list:
        """[(seconds, host span open at the gap's midpoint)], device 0."""
        if not self.ops:
            return []
        t, gaps = self.start, []
        busy = self._busy(min(self.ops)) + [[self.end, self.end]]
        for s, e in busy:
            if s > t:
                mid = (t + s) / 2
                label = "host.other"
                for hs, he, name in self.spans:
                    if hs <= mid <= he:
                        label = name
                gaps.append(((s - t) / 1e9, label))
            t = max(t, e)
        return gaps

    def breakdown(self) -> dict:
        """The device operations that took most time, and the idle time by
        what the host was doing, at most ``TOP`` of each."""
        by_op = defaultdict(float)
        for s, e, n in self.ops.get(min(self.ops), []) if self.ops else []:
            by_op[n] += (e - s) / 1e9
        by_host = defaultdict(float)
        for sec, label in self.idle_gaps():
            by_host[label] += sec
        top = lambda d: [[k, v] for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(by_op), "idle_gaps": top(by_host)}


def summarize(profile) -> Summary:
    """Reduce a ``ProfileData`` to the window's operations and spans."""
    window, spans = None, []
    ops, modules = defaultdict(list), defaultdict(list)
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                into = (ops if line.name == OPS_LINE else modules)[
                    int(m.group(1))]
                for ev in line.events:
                    into.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 op_name(ev.name)))
            elif not m:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW!r} event in the trace")
    s0, s1 = window
    clipped = {}
    for dev, evs in ops.items():
        clipped[dev] = sorted((max(s, s0), min(e, s1), n)
                              for s, e, n in evs if e > s0 and s < s1)
    spans = sorted((max(s, s0), min(e, s1), n) for s, e, n in spans
                   if e > s0 and s < s1)
    inside = {dev: sorted((s, e, n) for s, e, n in evs
                          if s0 <= s and e <= s1)
              for dev, evs in modules.items()}
    return Summary(s0, s1, clipped, spans, inside)
