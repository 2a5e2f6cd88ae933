"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

The traced stretch is the host annotation named by ``window`` (the runner
wraps it in ``jax.profiler.TraceAnnotation``). Inside it, per device:

* busy time, the union of the intervals in which an operation ran (the
  ``XLA Ops`` line of each ``/device:`` plane), and its idle share. Loops,
  conditionals and calls are containers: their span covers the ops inside
  them and the gaps between those, so they are not operations here;
* the device time of operations matched by name;
* idle gaps, each labelled with the host span (the program's ``obs`` spans,
  annotated into the same trace) that covers its midpoint.

Read with ``jax.profiler.ProfileData`` alone; timestamps are the trace's
own nanoseconds, on one clock for host and devices.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
OUTSIDE = "outside host spans"
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 f32[8,128] fusion``: the instruction, its shape and kind."""
    name, eq, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not eq:
        return name
    shape = ("tuple" if rest.startswith("(")
             else rest.split("{", 1)[0].split(" ", 1)[0])
    kind = re.search(r"\s([a-z][\w-]*)\(", rest)
    return f"{name} {shape} {kind.group(1) if kind else '?'}"


@dataclasses.dataclass
class Op:
    name: str
    start: float          # ns
    dur: float            # ns


@dataclasses.dataclass
class DeviceTrace:
    window: tuple[float, float]                 # ns
    devices: dict[str, list[Op]]                # plane -> ops in window
    host_spans: list[tuple[str, float, float]]  # (name, start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy_intervals(self, ops: list[Op]) -> list[tuple[float, float]]:
        out: list[tuple[float, float]] = []
        for op in sorted(ops, key=lambda o: o.start):
            if op.name.startswith(CONTAINERS):
                continue
            s = max(op.start, self.window[0])
            e = min(op.start + op.dur, self.window[1])
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], e))
            else:
                out.append((s, e))
        return out

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for ops in self.devices.values()
                  for s, e in self._busy_intervals(ops))
        return tot / 1e9 / len(self.devices)

    def op_seconds(self, match) -> float:
        """Device seconds of the ops whose name satisfies ``match``,
        averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(op.dur for ops in self.devices.values() for op in ops
                  if match(op.name))
        return tot / 1e9 / len(self.devices)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` ops with the most device time; loops and calls, which
        contain other ops, are left out."""
        agg: dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for op in ops:
                if not op.name.startswith(CONTAINERS):
                    agg[op.name] += op.dur
        k = max(len(self.devices), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9 / k] for name, ns in top]

    def span_at(self, t: float) -> str:
        """The host span (spans do not overlap) holding time ``t``."""
        i = bisect.bisect_right([s for _, s, _ in self.host_spans], t) - 1
        if i >= 0 and t < self.host_spans[i][2]:
            return self.host_spans[i][0]
        return OUTSIDE

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps of any device in the window, each
        named by the host span it fell in: ``[[span, seconds], ...]``."""
        gaps = []
        for ops in self.devices.values():
            t = self.window[0]
            for s, e in self._busy_intervals(ops) + [(self.window[1],) * 2]:
                if s > t:
                    gaps.append((s - t, self.span_at((s + t) / 2)))
                t = max(t, e)
        gaps.sort(key=lambda g: -g[0])
        return [[name, ns / 1e9] for ns, name in gaps[:n]]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str, *, window: str, spans: tuple[str, ...]) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    win = None
    host: list[tuple[str, float, float]] = []
    devices: dict[str, list[Op]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and win is None:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in spans:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Op(op_name(ev.name), ev.start_ns, ev.duration_ns)
                        for ev in line.events]
    if win is None:
        raise ValueError(f"no host annotation {window!r} in {path}")
    for name, ops in devices.items():
        devices[name] = [o for o in ops
                         if o.start < win[1] and o.start + o.dur > win[0]]
    host = sorted((h for h in host if h[1] < win[1] and h[2] > win[0]),
                  key=lambda h: h[1])
    return DeviceTrace(window=win, devices=devices, host_spans=host)
