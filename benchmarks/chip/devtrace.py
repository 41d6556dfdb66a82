"""Reduction of a profiler trace to what the per-layer metrics read.

`load(directory)` reads the newest `.xplane.pb` under a
`jax.profiler` trace directory with `jax.profiler.ProfileData`
and returns a `Trace`:

  ops      per device plane, the device's operation events (the
           "XLA Ops" line): what ran on the chip, and when;
  modules  per device plane, the executions of whole compiled programs
           (the "XLA Modules" line);
  spans    the host annotations the drivers write
           (`jax.profiler.TraceAnnotation`, names starting "bench.").

Every time is in seconds on the profiler's one clock.  The traced
window is the driver's "bench.window" span.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self):
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    spans: List[Event]

    @property
    def window(self) -> Tuple[float, float]:
        wins = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
        w = max(wins, key=lambda s: s.dur)
        return w.start, w.end

    @property
    def window_s(self) -> float:
        t0, t1 = self.window
        return t1 - t0

    def devices(self) -> List[str]:
        return sorted(self.ops)


def _is_device_plane(name):
    return name.startswith("/device:") and "CPU" not in name


def from_profile(profile) -> Trace:
    """Reduce a `jax.profiler.ProfileData` to a `Trace`."""
    ops, modules, spans = {}, {}, []
    for plane in profile.planes:
        device = _is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                dest = ops if line.name == OPS_LINE else modules
                dest[plane.name] = sorted(
                    (Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                     for e in line.events), key=lambda e: e.start)
            elif not device:
                spans.extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def newest_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(directory) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(newest_xplane(directory))))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def clip(events, lo, hi) -> List[Event]:
    """The parts of `events` inside [lo, hi]."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events) -> List[Tuple[float, float]]:
    """Merged busy intervals of possibly overlapping events."""
    merged = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            if e.end > merged[-1][1]:
                merged[-1][1] = e.end
        else:
            merged.append([e.start, e.end])
    return [(s, t) for s, t in merged]


def busy_s(trace: Trace, device: str) -> float:
    """Seconds of the window in which some operation ran on `device`."""
    lo, hi = trace.window
    return sum(t - s for s, t in union(clip(trace.ops[device], lo, hi)))


def mean_busy_s(trace: Trace) -> float:
    devs = trace.devices()
    if not devs:
        raise ValueError("the trace holds no device operations")
    return sum(busy_s(trace, d) for d in devs) / len(devs)


def op_seconds(trace: Trace, device: str) -> Dict[str, float]:
    """Device seconds per operation name inside the window."""
    lo, hi = trace.window
    out: Dict[str, float] = {}
    for e in clip(trace.ops[device], lo, hi):
        out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def matching_seconds(trace: Trace, device: str, match) -> float:
    """Device seconds inside the window of the operations whose name
    satisfies `match` (overlaps between them counted once)."""
    lo, hi = trace.window
    hits = [e for e in clip(trace.ops[device], lo, hi) if match(e.name)]
    return sum(t - s for s, t in union(hits))


def module_runs(trace: Trace, device: str, match=lambda n: True):
    """Executions of compiled programs inside the window."""
    lo, hi = trace.window
    return [e for e in trace.modules.get(device, [])
            if lo <= e.start < hi and match(e.name)]


def idle_gaps(trace: Trace, device: str) -> List[Tuple[str, float]]:
    """Every idle gap of `device` inside the window, longest first,
    each named by the innermost host span that covers its midpoint
    ("host.none" where no span does)."""
    lo, hi = trace.window
    busy = union(clip(trace.ops[device], lo, hi))
    gaps, cursor = [], lo
    for s, t in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, t)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
    out = []
    for s, t in gaps:
        mid = (s + t) / 2
        cover = [sp for sp in spans if sp.start <= mid < sp.end]
        name = min(cover, key=lambda sp: sp.dur).name if cover \
            else "host.none"
        out.append((name, t - s))
    out.sort(key=lambda g: -g[1])
    return out


def short_name(hlo):
    """"%fusion.3 = f32[..] fusion(...), kind=.." -> "fusion.3 fusion";
    a custom call adds its target.  Names that are not HLO text pass."""
    if " = " not in hlo or not hlo.startswith("%"):
        return hlo
    name, rest = hlo[1:].split(" = ", 1)
    depth, i = 0, 0
    for i, ch in enumerate(rest):       # skip the result shape
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{name} {opcode}" + (f" {target.group(1)}" if target else "")


def breakdown(trace: Trace, top=10) -> dict:
    """The device operations that took most time and the longest idle
    gaps, over every device of the trace (seconds summed over
    devices for operations; gaps listed per device)."""
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for d in trace.devices():
        for name, sec in op_seconds(trace, d).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + sec
        gaps.extend(idle_gaps(trace, d))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
