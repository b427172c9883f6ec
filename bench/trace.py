"""Reduction of a profiler trace of the measured window to device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData``.  A TPU shows up as planes named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per HLO
operation the device ran, named by the operation's HLO text, with its
start and duration in nanoseconds on the same clock as the host planes.
Control-flow operations (a ``while`` of ``lax.map``) enclose the events
of the operations they run; the leaves are the operations that do the
work.  The harness marks the window with a ``TraceAnnotation`` named
``bench.window`` on the host.

* ``busy_s``: the union of the leaf operations' intervals of each
  device inside the window, averaged over the devices;
* ``window_s``: the length of the window annotation;
* ``op_s``: self seconds (less enclosed operations) per operation,
  named by the HLO instruction without its number, summed over the
  devices;
* ``kernel_s(*patterns)``: seconds of the operations whose HLO text
  contains every pattern;
* ``idle_gaps``: the intervals inside the window in which no device
  operation ran, each named by the host activity that covers it
  (spans given on the host clock with an offset to trace time).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {paths}")
    return paths[0]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduced:
    window: tuple[float, float]                 # ns, trace clock
    busy: list = field(default_factory=list)    # per device: union (ns)
    ops: list = field(default_factory=list)     # (name, start, end, self)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in u) for u in self.busy) \
            / len(self.busy) / 1e9

    def op_s(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for name, _, _, own in self.ops:
            key = short_name(name)
            out[key] = out.get(key, 0.0) + own / 1e9
        return out

    def kernel_s(self, *patterns: str) -> float:
        return sum((e - s) / 1e9 for name, s, e, _ in self.ops
                   if all(p in name for p in patterns))

    def idle_gaps(self, host_spans: list[tuple[str, float, float]],
                  offset_s: float) -> list[tuple[str, float]]:
        """Gaps of the first device's busy union inside the window,
        longest first: ``(what the host did, seconds)``, where what the
        host did lists the seconds of the gap each kind of host span
        covers (``trace:1.20s+lower:0.90s+other:0.10s``).
        ``host_spans`` are ``(label, start, end)`` on the host clock;
        ``offset_s`` is the host time of trace time 0."""
        busy = self.busy[0] if self.busy else []
        w0, w1 = self.window
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, min(s, w1)))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        spans = [(label, (s - offset_s) * 1e9, (e - offset_s) * 1e9)
                 for label, s, e in host_spans]
        out = []
        for g0, g1 in gaps:
            if g1 <= g0:
                continue
            inside = [(label, max(s, g0), min(e, g1))
                      for label, s, e in spans if min(e, g1) > max(s, g0)]
            cover = {}
            for label in sorted({lb for lb, _, _ in inside}):
                cover[label] = sum(e - s for s, e in _union(
                    [(s, e) for lb, s, e in inside if lb == label]))
            cover["other"] = (g1 - g0) - sum(
                e - s for s, e in _union([(s, e) for _, s, e in inside]))
            name = "+".join(f"{label}:{ns / 1e9:.2f}s" for label, ns in
                            sorted(cover.items(), key=lambda kv: -kv[1])
                            if ns > 0)
            out.append((name, (g1 - g0) / 1e9))
        return sorted(out, key=lambda x: -x[1])


def reduce(path: str) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb``) to the window's
    device activity.  Raises if there is no window annotation or no
    device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    device_lines = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    if not device_lines:
        raise ValueError(f"no TPU device plane with an {OPS_LINE!r} line "
                         f"in {path}")
    red = Reduced(window=window)
    w0, w1 = window
    for line in device_lines:
        ivs = []
        for name, s, e, own, leaf in nest(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in line.events):
            cs, ce = max(s, w0), min(e, w1)
            if ce <= cs:
                continue
            red.ops.append((name, cs, ce, own * (ce - cs) / (e - s)))
            if leaf:
                ivs.append((cs, ce))
        red.busy.append(_union(ivs))
    return red


def nest(events) -> list[tuple[str, float, float, float, bool]]:
    """``(name, start, end, self, is_leaf)`` of events ``(start, end,
    name)`` of one line, where an event that starts inside another is
    enclosed by it: self time is the duration less the enclosed
    events'."""
    events = sorted(events, key=lambda t: (t[0], -t[1]))
    own = [e - s for s, e, _ in events]
    leaf = [True] * len(events)
    stack: list[int] = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(name, s, e, o, lf)
            for (s, e, name), o, lf in zip(events, own, leaf)]


def short_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)

