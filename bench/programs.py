"""Device time put down to the engine's programs, idle gaps to its phases.

Reads the same ``.xplane.pb`` as :mod:`bench.trace_reduce` and adds what
that reduction leaves out:

* each device plane's ``XLA Modules`` line: every op of ``XLA Ops`` belongs
  to the program execution that holds its start, and the program's layer is
  ``repro.serve.batched.PROGRAMS`` of its module name (``other`` for a
  module the table does not list);
* the engine's phase spans (its ``obs`` spans, annotated into the host
  plane), on the trace's own clock, so each idle gap is named by the
  innermost phase the host was in at the gap's midpoint.

A program without the ``PROGRAMS`` table, or a trace without phases, gives
None where a reader needs them: such readers then report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re
import sys
from collections import defaultdict
from pathlib import Path

from bench import harness
from bench import trace_reduce as TR

MODULES_LINE = "XLA Modules"
OTHER = "other"
OUTSIDE = TR.OUTSIDE
# the engine's phase spans (repro.serve.batched, engine row): the leaves
# tile the job; round.* nest in round, prefill.* in prefill and
# prefill_group, and the prefills in schedule (or harvest, when a
# preemption admits)
LEAVES = ("schedule", "harvest", "round.prep", "round.launch", "round.wait",
          "prefill.launch", "prefill.store", "prefill.wait")
PHASES = LEAVES + ("round", "prefill", "prefill_group")
# below this share of device op time in the table's programs, a program the
# table misses would leave its time out of every program-second denominator
MIN_ATTRIBUTED = 0.99
# where the serve_offline runner leaves the traced job's profile
TRACE_DIR = harness.ROOT / ".bench_out" / "trace"
JOB_SPAN = "bench_job"


def module_name(text: str) -> str:
    """``jit_round_fn(3854724545798087085)`` -> ``jit_round_fn``."""
    return re.sub(r"\(\d+\)$", "", text)


def program_table() -> dict[str, str] | None:
    """The program's ``PROGRAMS`` table, or None where it has none."""
    try:
        from repro.serve import batched
    except ImportError:
        return None
    return getattr(batched, "PROGRAMS", None)


@dataclasses.dataclass
class ProgramOp(TR.Op):
    program: str = ""             # XLA module name; "" outside every program
    layer: str = OTHER


@dataclasses.dataclass
class ProgramTrace(TR.DeviceTrace):
    """A :class:`~bench.trace_reduce.DeviceTrace` whose ops carry their
    program's layer, with the program executions and the engine's phases.
    ``host_spans`` holds the phases (nested, so they overlap)."""
    # plane -> program executions (start, end ns, module name, layer)
    modules: dict[str, list[tuple[float, float, str, str]]] = \
        dataclasses.field(default_factory=dict)

    def program_seconds(self, layer: str) -> float:
        """Device seconds of the executions of ``layer``'s programs inside
        the window, averaged over the devices."""
        if not self.modules:
            return 0.0
        lo, hi = self.window
        tot = sum(max(0.0, min(e, hi) - max(s, lo))
                  for mods in self.modules.values()
                  for s, e, _, name in mods if name == layer)
        return tot / 1e9 / len(self.modules)

    def attributed_share(self) -> float:
        """Share of the device op time (containers left out) that belongs
        to a program of the table."""
        tot = named = 0.0
        for ops in self.devices.values():
            for op in ops:
                if not op.name.startswith(TR.CONTAINERS):
                    tot += op.dur
                    named += op.dur if op.layer != OTHER else 0.0
        return named / tot if tot else 0.0

    def top_ops(self, n: int = 10) -> list[list]:
        """As :meth:`DeviceTrace.top_ops`, keyed on (program layer, op):
        ``round/fusion.3 f32[8,128] fusion``."""
        agg: dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for op in ops:
                if not op.name.startswith(TR.CONTAINERS):
                    agg[f"{op.layer}/{op.name}"] += op.dur
        k = max(len(self.devices), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9 / k] for name, ns in top]

    @functools.cached_property
    def _index(self):
        """Phase starts, and the latest end among the phases up to each."""
        starts, reach, end = [], [], float("-inf")
        for _, s, e in self.host_spans:
            starts.append(s)
            end = max(end, e)
            reach.append(end)
        return starts, reach

    def span_at(self, t: float) -> str:
        """The innermost phase holding ``t``: phases nest, so that is the
        latest to start of those that hold it."""
        starts, reach = self._index
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and reach[i] > t:
            name, _, e = self.host_spans[i]
            if t < e:
                return name
            i -= 1
        return OUTSIDE

    def phase_seconds(self, names, minus=()) -> float:
        """Seconds of the window inside a phase of ``names`` and outside
        every phase of ``minus`` (a layer's own time, its children cut)."""
        return (_measure(self._union(names) + self._union(minus))
                - _measure(self._union(minus))) / 1e9

    def _union(self, names) -> list[tuple[float, float]]:
        lo, hi = self.window
        return [(max(s, lo), min(e, hi)) for n, s, e in self.host_spans
                if n in names and min(e, hi) > max(s, lo)]


def _measure(spans) -> float:
    tot, end = 0.0, float("-inf")
    for s, e in sorted(spans):
        s = max(s, end)
        if e > s:
            tot += e - s
            end = e
    return tot


def reduce(path: str, *, window: str = JOB_SPAN,
           programs: dict[str, str]) -> ProgramTrace:
    """One pass over the trace: the window, the phases, every device op
    with its program's layer, and the program executions."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    win = None
    phases: list[tuple[str, float, float]] = []
    devices: dict[str, list[ProgramOp]] = {}
    modules: dict[str, list[tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and win is None:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in PHASES:
                        phases.append((ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if TR.OPS_LINE not in lines:
                continue
            mods = []
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ()):
                mod = module_name(ev.name)
                mods.append((ev.start_ns, ev.start_ns + ev.duration_ns, mod,
                             programs.get(mod, OTHER)))
            mods.sort()
            starts = [m[0] for m in mods]
            ops = []
            for ev in lines[TR.OPS_LINE].events:
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                _, _, mod, layer = (mods[i] if i >= 0
                                    and ev.start_ns < mods[i][1]
                                    else (0, 0, "", OTHER))
                ops.append(ProgramOp(TR.op_name(ev.name), ev.start_ns,
                                     ev.duration_ns, mod, layer))
            devices[plane.name] = ops
            modules[plane.name] = mods
    if win is None:
        raise ValueError(f"no host annotation {window!r} in {path}")
    lo, hi = win
    for name, ops in devices.items():
        devices[name] = [o for o in ops
                         if o.start < hi and o.start + o.dur > lo]
        modules[name] = [m for m in modules[name] if m[0] < hi and m[1] > lo]
    # by start; a parent before a child that starts with it
    phases = sorted((p for p in phases if p[1] < hi and p[2] > lo),
                    key=lambda p: (p[1], -p[2]))
    return ProgramTrace(window=win, devices=devices, host_spans=phases,
                        modules=modules)


_CACHE: dict[tuple, ProgramTrace] = {}


def of(ctx) -> ProgramTrace | None:
    """The traced job of a reader's context with its programs and phases:
    ``ctx.programs`` where the context carries one, else the runner's
    profile reduced again, or None where the program has no ``PROGRAMS``
    table or the profile is not the context's job."""
    pt = getattr(ctx, "programs", None)
    if pt is not None:
        return pt
    table = program_table()
    if table is None:
        return None
    try:
        path = TR.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (path, Path(path).stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(path, programs=table)
    pt = _CACHE[key]
    return pt if tuple(pt.window) == tuple(ctx.device.window) else None


def attributed(ctx) -> ProgramTrace | None:
    """:func:`of`, or None where less than :data:`MIN_ATTRIBUTED` of the
    device op time belongs to a program of the table: the readers that
    divide by program seconds then report nothing rather than a share
    inflated by work that moved out of the listed programs."""
    pt = of(ctx)
    if pt is None:
        return None
    share = pt.attributed_share()
    if share < MIN_ATTRIBUTED:
        log(f"programs: {100 * share!r}% of device op time in PROGRAMS, "
            f"under {100 * MIN_ATTRIBUTED!r}%: no program-second metric")
        return None
    return pt


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
