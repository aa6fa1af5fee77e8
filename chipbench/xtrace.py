"""Reduction of a JAX profiler trace (``.xplane.pb``) to device intervals.

A TPU's plane (``/device:TPU:<n>``) carries an ``XLA Modules`` line, one
event per execution of a compiled program (``jit_<name>(<id>)``), and an
``XLA Ops`` line, one event per operation.  The host plane carries the
benchmark's ``jax.profiler.TraceAnnotation`` spans and JAX's own host
events.  Everything here is arithmetic on those intervals; the per-layer
metric readers in ``metrics/`` pick what they need by name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import warnings
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "chipbench.window"


@dataclass(frozen=True)
class Event:
    name: str
    start: int            # ns
    end: int              # ns
    line: str = ""
    stats: tuple = ()

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list
    host: list

    def window(self, name: str = WINDOW) -> tuple[int, int] | None:
        ev = [e for e in self.host if e.name == name]
        if not ev:
            return None
        return min(e.start for e in ev), max(e.end for e in ev)


def module_name(event_name: str) -> str:
    """``jit__ivf_candidates(123)`` → ``jit__ivf_candidates``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """An op event is named by its HLO instruction,
    ``%fusion.2 = f32[144015360]{0:T(1024)} fusion(...)``; this gives
    ``fusion.2 f32[144015360]`` (name and result type, no layouts)."""
    text = re.sub(r"{[^}]*}", "", event_name)
    m = re.match(r"%?(\S+) = (\([^)]*\)|\S+)", text)
    return f"{m.group(1)} {m.group(2)}"[:120] if m else text[:120]


def _events(line, line_name: str) -> list:
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for e in line.events:
            start = int(e.start_ns)
            stats = tuple((str(k), v) for k, v in e.stats)
            out.append(Event(e.name, start, start + int(e.duration_ns),
                             line_name, stats))
    return out


def find(log_dir: str) -> str | None:
    """Newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line, line.name)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line, line.name)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += _events(line, line.name)
    for d in devices:
        d.ops.sort(key=lambda e: e.start)
        d.modules.sort(key=lambda e: e.start)
    return Trace(devices=devices, host=host)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: Device, lo: int, hi: int) -> int:
    """Time in [lo, hi) in which some operation ran on the device."""
    return sum(e - s for s, e in union(((o.start, o.end) for o in dev.ops),
                                       lo, hi))


@dataclass(frozen=True)
class Run:
    """One execution of a compiled program, with the ops inside it."""

    device: str
    start: int
    end: int
    ops: tuple

    @property
    def busy(self) -> int:
        return sum(e - s for s, e in union(((o.start, o.end)
                                            for o in self.ops),
                                           self.start, self.end))


def module_runs(trace: Trace, module: str, lo: int, hi: int) -> list[Run]:
    """Executions of programs named ``module`` that start in [lo, hi)."""
    runs = []
    for dev in trace.devices:
        for m in dev.modules:
            if module_name(m.name) != module or not lo <= m.start < hi:
                continue
            ops = tuple(o for o in _ops_from(dev, m.start, m.end)
                        if o.end <= m.end)
            runs.append(Run(dev.name, m.start, m.end, ops))
    return runs


def _ops_from(dev: Device, start: int, end: int) -> list:
    """Ops of dev that start in [start, end) (ops are sorted by start)."""
    starts = [o.start for o in dev.ops]
    return dev.ops[bisect.bisect_left(starts, start):
                   bisect.bisect_left(starts, end)]


def module_of(dev: Device, op: Event) -> str:
    """Name of the program execution that holds op, else "?"."""
    i = bisect.bisect_right([m.start for m in dev.modules], op.start) - 1
    if i >= 0 and op.end <= dev.modules[i].end:
        return module_name(dev.modules[i].name)
    return "?"


def top_ops(trace: Trace, lo: int, hi: int, n: int = 10) -> list:
    """[[module/op, seconds]] of the ops that took most device time,
    summed over the chips' planes."""
    total: dict[str, int] = {}
    for dev in trace.devices:
        for o in dev.ops:
            if lo <= o.start < hi:
                key = f"{module_of(dev, o)}/{op_name(o.name)}"
                total[key] = total.get(key, 0) + o.dur
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def _host_activity(host: list, t: int) -> str:
    """Name of the innermost host event that covers instant t."""
    best = None
    for e in host:
        if e.start <= t < e.end and e.name != WINDOW and (
                best is None or e.dur < best.dur):
            best = e
    return best.name if best is not None else "host:none"


def idle_gaps(trace: Trace, lo: int, hi: int, n: int = 10) -> list:
    """[[what the host was doing, seconds]] for the longest spans of [lo, hi)
    in which no operation ran on the first device.  What the host was doing
    is the innermost event, at the middle of the gap, on the thread that
    holds the benchmark's window annotation (the Python thread)."""
    if not trace.devices:
        return []
    lines = {e.line for e in trace.host if e.name == WINDOW}
    host = [e for e in trace.host if e.line in lines]
    busy = union(((o.start, o.end) for o in trace.devices[0].ops), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_activity(host, (s + e) // 2), (e - s) / 1e9]
            for s, e in gaps[:n]]
