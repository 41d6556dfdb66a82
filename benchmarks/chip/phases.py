"""Where the API-BCD superstep's time goes, phase by phase.

The program names its phases itself: `jax.named_scope` in
`repro.dist.trainer.make_train_step` (apibcd.grad, .accumulate, .zsum,
.prox, .select, .token, .exchange) and in
`repro.models.transformer.train_loss` (model.embed, .blocks, .head)
lands in the compiled step as each HLO instruction's `op_name`, and
`repro.launch.train.Superstep` writes host spans ("apibcd.step" around
each dispatch, "apibcd.batch_upload" around its own uploads).

  op_names(hlo)     {instruction: op_name} of a compiled program's
                    optimised HLO text; a fusion counts under its
                    root's op_name
  self_seconds(..)  each device operation's self time inside the
                    window: an operation that holds others on the same
                    line (a `while` around its body) keeps only the
                    time in which none of them runs, so the per-device
                    sum is the busy time
  reduce(..)        the step's device time per scope, per device
  of(cell, trace)   the reduction of a traced run of a training cell
                    (the per-layer readers `superstep_*` read it)

Device operations are attributed to the step's program by time
containment in the "XLA Modules" line.  The trace's operation events
carry no `op_name`, so it comes from the step's optimised HLO, taken
after the window by compiling an abstract `Superstep` at the cell's
shapes (a compile-cache hit).  A program that names no `apibcd.*`
scope reads None throughout.
"""
from __future__ import annotations

import dataclasses
import re
import sys
import traceback
from collections import defaultdict
from typing import Dict, List, Optional

import devtrace
from devtrace import Event, Trace

PREFIX = "apibcd."
STEP_SPAN = "apibcd.step"
GRAD = ("grad",)
UPDATE = ("accumulate", "zsum", "prox", "select", "token")
EXCHANGE = ("exchange",)
PHASES = GRAD + UPDATE + EXCHANGE
OTHER = "other"            # the step's ops under no apibcd.* scope
OUTSIDE = "outside"        # ops of any other program

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,}]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_PHASE = re.compile(r"apibcd\.([A-Za-z_]+)")
_MODEL = re.compile(r"model\.([A-Za-z_]+)")


def op_names(hlo):
    """(module name, {instruction: op_name}) of optimised HLO text.

    An instruction without an op_name that calls a computation (a
    fusion whose own metadata was dropped) takes the op_name of that
    computation's root, or, where the root has none (a tuple of
    outputs), of the last instruction there that has one."""
    module, comp = None, None
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}               # computation -> its ROOT
    named: Dict[str, List[str]] = defaultdict(list)
    for line in hlo.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        on = _OP_NAME.search(line)
        if on:
            own[name] = on.group(1)
            named[comp].append(name)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        if line.lstrip().startswith("ROOT "):
            root[comp] = name
    out = dict(own)
    for name, comp in calls.items():
        if name in own:
            continue
        if root.get(comp) in own:
            out[name] = own[root[comp]]
        elif named.get(comp):
            out[name] = own[named[comp][-1]]
    return module, out


def instruction(event_name):
    """"%fusion.3 = f32[..] fusion(..)" -> "fusion.3"; other names pass."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def scope(op_name):
    """op_name -> (phase, model scope, direction); phase is None outside
    every apibcd.* scope, direction one of forward, backward,
    recompute (forward work redone inside the backward pass) or ""."""
    if not op_name:
        return None, None, ""
    p = _PHASE.search(op_name)
    mdl = _MODEL.search(op_name)
    if "rematted_computation" in op_name:
        d = "recompute"
    elif "transpose(" in op_name:
        d = "backward"
    elif "jvp(" in op_name:
        d = "forward"
    else:
        d = ""
    return (p.group(1) if p else None, mdl.group(1) if mdl else None, d)


def self_seconds(events, lo, hi):
    """[(event, self seconds)] of `events` clipped to [lo, hi].  Each
    instant in which some event runs goes to the one that started last
    among those running (the innermost), so the sum is the union."""
    evs = sorted(devtrace.clip(events, lo, hi),
                 key=lambda e: (e.start, -e.dur))
    own = [0.0] * len(evs)
    stack: List[int] = []
    t = lo

    def pop_until(limit):
        nonlocal t
        while stack and evs[stack[-1]].end <= limit:
            i = stack.pop()
            if evs[i].end > t:
                own[i] += evs[i].end - t
                t = evs[i].end

    for i, e in enumerate(evs):
        pop_until(e.start)
        if stack and e.start > t:
            own[stack[-1]] += e.start - t
        t = max(t, e.start)
        stack.append(i)
    pop_until(float("inf"))
    return list(zip(evs, own))


@dataclasses.dataclass
class Phases:
    """The step's device seconds inside the window, per device:
    `seconds[device][key]` where key is a phase of PHASES, OTHER or
    OUTSIDE; `detail[device][(phase, model, direction)]`; `ops`, self
    seconds per (key, op name) summed over the devices; `steps[device]`, the
    step program's runs inside the window; `spans`, the program's host
    spans; `host_steps`, the durations of the "apibcd.step" spans that
    start inside the window."""
    seconds: Dict[str, Dict[str, float]]
    detail: Dict[str, Dict[tuple, float]]
    ops: Dict[tuple, float]
    steps: Dict[str, int]
    spans: List[Event]
    host_steps: List[float]
    scoped: bool            # some op of the step carries an apibcd scope

    def host_step_ms(self) -> Optional[float]:
        """Mean ms of the "apibcd.step" spans; None where there are none."""
        if not self.host_steps:
            return None
        return 1e3 * sum(self.host_steps) / len(self.host_steps)

    def per_step_ms(self, keys) -> Optional[float]:
        """ms per step of `keys`, the mean over the devices that ran
        the step; None where nothing ran or nothing is scoped."""
        vals = [1e3 * sum(self.seconds[d].get(k, 0.0) for k in keys)
                / n for d, n in self.steps.items() if n]
        if not vals or not self.scoped:
            return None
        return sum(vals) / len(vals)


def reduce(trace: Trace, module: str, names: Dict[str, str],
           program_spans=()) -> Phases:
    """Attribute each device's busy time inside the window to the
    step's scopes.  `module` is the step's HLO module name (its runs
    in the "XLA Modules" line are "<module>(<id>)"), `names` its
    {instruction: op_name}."""
    lo, hi = trace.window
    seconds, detail, steps = {}, {}, {}
    ops: Dict[tuple, float] = defaultdict(float)
    scoped = False
    for dev in trace.devices():
        runs = sorted((r for r in devtrace.module_runs(trace, dev)
                       if r.name.split("(", 1)[0] == module),
                      key=lambda r: r.start)
        steps[dev] = len(runs)
        sec: Dict[str, float] = defaultdict(float)
        det: Dict[tuple, float] = defaultdict(float)
        j = 0
        for e, own in self_seconds(trace.ops[dev], lo, hi):
            mid = e.start + e.dur / 2
            while j < len(runs) and runs[j].end < mid:
                j += 1
            if j < len(runs) and runs[j].start <= mid:
                phase, mdl, direction = scope(names.get(instruction(e.name)))
                scoped = scoped or phase is not None
                key = phase if phase in PHASES else OTHER
                det[(phase, mdl, direction)] += own
            else:
                key = OUTSIDE
            sec[key] += own
            ops[(key, devtrace.short_name(e.name))] += own
        seconds[dev], detail[dev] = dict(sec), dict(det)
    spans = list(program_spans)
    host = [s.dur for s in spans if s.name == STEP_SPAN and lo <= s.start < hi]
    return Phases(seconds, detail, dict(ops), steps, spans, host, scoped)


def program_spans(profile) -> List[Event]:
    """The host spans of the program ("apibcd.*") in a
    `jax.profiler.ProfileData`."""
    out = []
    for plane in profile.planes:
        if devtrace._is_device_plane(plane.name):
            continue
        for line in plane.lines:
            out.extend(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events if e.name.startswith(PREFIX))
    return sorted(out, key=lambda e: e.start)


def idle_gaps(trace: Trace, device, spans=()):
    """`devtrace.idle_gaps` with the program's spans beside the
    driver's: a gap is named by the innermost span of either."""
    both = dataclasses.replace(
        trace, spans=sorted(list(trace.spans) + list(spans),
                            key=lambda e: e.start))
    return devtrace.idle_gaps(both, device)


def step_hlo(cell):
    """The optimised HLO text of the cell's step, as the training
    driver builds it, compiled from shapes (nothing is allocated)."""
    from repro.launch.train import Superstep

    t = cell.traffic
    run = Superstep(cell.arch(), cell.devices[:cell.chips],
                    agents=t["agents"], walks=t["walks"],
                    batch_per_agent=t["batch_per_agent"], seq=t["seq"],
                    tau=t["tau"], rho=t["rho"], seed=cell.model_seed,
                    place=False)
    return run.lower(run.abstract_batch()).compile().as_text()


def traced_profile():
    """The traced run's profile: the newest trace file in the harness's
    trace directory, read again (`devtrace.Trace` keeps only the
    driver's spans)."""
    from jax.profiler import ProfileData
    from run import TRACE_DIR
    return ProfileData.from_file(str(devtrace.newest_xplane(TRACE_DIR)))


def of(cell, trace) -> Optional[Phases]:
    """The phase reduction of a traced training run, computed once per
    trace and kept on it; its table goes to stderr.  None where the
    program names no apibcd scope or the reduction cannot be made (the
    reason goes to stderr): a reader of optional instrumentation must
    not end the run."""
    if hasattr(trace, "_phases"):
        return trace._phases
    found = None
    try:
        module, names = op_names(step_hlo(cell))
        found = reduce(trace, module, names,
                       program_spans(traced_profile()))
        print(table(found, trace), file=sys.stderr, flush=True)
        if not found.scoped:
            found = None
    except Exception:           # noqa: BLE001 -- see the docstring
        print("phases: no reduction\n" + traceback.format_exc(),
              file=sys.stderr, flush=True)
    trace._phases = found
    return found


def table(ph: Phases, trace: Trace, top=4) -> str:
    """ms per step of each scope, device by device, the longest ops of
    each, and the longest idle gaps named by the innermost span of the
    driver's or the program's, for whoever reads the run's log (no
    metric reads it)."""
    lines = []
    for dev, n in ph.steps.items():
        if not n:
            lines.append(f"phases {dev}: no step ran in the window")
            continue
        busy = devtrace.busy_s(trace, dev)
        sec = ph.seconds[dev]
        lines.append(f"phases {dev}: {n} steps, busy "
                     f"{1e3 * busy / n:.4f} ms/step, sum of scopes "
                     f"{1e3 * sum(sec.values()) / n:.4f}")
        for key in PHASES + (OTHER, OUTSIDE):
            if key in sec:
                name = PREFIX + key if key in PHASES else key
                lines.append(f"  {name:<22} {1e3 * sec[key] / n:10.4f}")
        for (phase, mdl, d), s in sorted(ph.detail[dev].items(),
                                         key=lambda kv: -kv[1]):
            if phase == "grad":
                label = f"model.{mdl}" if mdl else "(no model scope)"
                lines.append(f"    grad {label:<18} {d or '-':<10} "
                             f"{1e3 * s / n:10.4f}")
        gaps = idle_gaps(trace, dev, ph.spans)[:top]
        lines.append("  longest idle gaps, ms: " + ", ".join(
            f"{name} {1e3 * g:.4f}" for name, g in gaps))
    steps = sum(ph.steps.values()) or 1
    lines.append("phases: longest ops of each scope, ms per step "
                 "(self time)")
    for key in PHASES + (OTHER, OUTSIDE):
        mine = sorted(((n, sec) for (k, n), sec in ph.ops.items()
                       if k == key), key=lambda kv: -kv[1])
        if not mine:
            continue
        by_opcode: Dict[str, float] = defaultdict(float)
        for name, sec in mine:
            by_opcode[" ".join(name.split()[1:]) or name] += sec
        lines.append(f"  {key} by opcode: " + ", ".join(
            f"{op} {1e3 * sec / steps:.4f}" for op, sec in
            sorted(by_opcode.items(), key=lambda kv: -kv[1])[:2 * top]))
        label = PREFIX + key if key in PHASES else key
        for name, sec in mine[:top]:
            lines.append(f"  {1e3 * sec / steps:10.4f}  {label} "
                         f"{name[:90]}")
    host = ph.host_step_ms()
    if host is not None:
        lines.append(f"phases: {len(ph.host_steps)} {STEP_SPAN} spans in "
                     f"the window, mean {host:.4f} ms")
    return "\n".join(lines)
