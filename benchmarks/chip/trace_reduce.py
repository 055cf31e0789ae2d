"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read: device busy time and idle share, device time per
XLA module and per op, the host spans the benchmark annotated, and the
idle gaps of the device attributed to what the host was doing in them.

Planes: each ``/device:<chip>:<n>`` plane is one chip; its ``XLA Ops`` line
holds one event per op execution (busy time is their union; a control-flow
op such as a layer loop's ``while`` contains the ops of its body) and its
``XLA Modules`` line one event per program execution.  Host spans are the
``bench:*`` annotations on the ``/host:CPU`` plane, each with the stats it
was annotated with.  The program's own spans (``alise.*``, written by
``serving/observability/span.py``) are kept apart, in ``program``, each
with its stats and its host thread: ``program_spans`` and ``within`` read
them, and nothing that gives a program its role or an idle gap its span
sees them.  All events share the trace's clock (nanoseconds).  The
traced window is the span of the ``bench:window`` annotation.

Programs are told apart by what the host was doing when it dispatched
them, not by name: a jitted ``functools.partial`` runs as
``jit__unknown``.  Each program execution carries a ``run_id``, and so
does the runtime's ``DoEnqueueProgram`` event that launched it.  That
event may come after the dispatching call returned (it waits for the
inputs' transfers), so it is traced back along the runtime's flow ids:
it sits inside a ``tpu::System::Execute=>IssueSequencedEvent`` event that
consumes the flow a ``tpu::System::Execute`` event produced during the
dispatching call.  The innermost ``bench:*`` span open at that call gives
the execution a role (``decode``, ``prefill``, ...), and each program
takes the role most of its executions had.  Where the chain is missing,
the enqueue's own time stands in.
"""
from __future__ import annotations

import bisect
import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

PREFIX = "bench:"
PROGRAM = "alise."              # the program's own spans
WINDOW = PREFIX + "window"
NO_SPAN = "no host span"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Event:
    name: str
    start: float                # seconds on the trace's clock
    dur: float
    stats: Dict[str, object] = field(default_factory=dict)
    thread: int = 0             # the host line (thread) it was traced on

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Timeline:
    """What a trace holds that the metrics use, on one clock."""
    window: Tuple[float, float]
    ops: Dict[str, List[Event]]         # chip -> op executions
    modules: Dict[str, List[Event]]     # chip -> program executions
    host: List[Event]                   # bench:* spans, prefix dropped
    enqueued: Dict[str, float] = field(default_factory=dict)  # run_id -> t
    program: List[Event] = field(default_factory=list)  # alise.*, ditto

    def __post_init__(self):
        spans = sorted((e for e in self.host if e.name != WINDOW[len(PREFIX):]),
                       key=lambda e: e.start)
        self._spans = spans
        self._starts = [e.start for e in spans]
        self._reach, reach = [], float("-inf")   # latest end so far
        for e in spans:
            reach = max(reach, e.end)
            self._reach.append(reach)
        self._roles: Optional[Dict[str, str]] = None
        self._named: Dict[str, List[Event]] = defaultdict(list)
        for e in sorted(self.program, key=lambda e: e.start):
            self._named[e.name].append(e)
        self._named_starts = {name: [e.start for e in evs]
                              for name, evs in self._named.items()}

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _clip(ev: Event, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(ev.start, lo), min(ev.end, hi)
    return (a, b) if b > a else None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_intervals(tl: Timeline, chip: str) -> List[Tuple[float, float]]:
    lo, hi = tl.window
    evs = tl.ops.get(chip) or tl.modules.get(chip) or []
    return union([iv for iv in (_clip(e, lo, hi) for e in evs) if iv])


def busy_s(tl: Timeline) -> float:
    """Seconds in which an op ran, averaged over the chips that ran any."""
    chips = [c for c in set(tl.ops) | set(tl.modules)
             if busy_intervals(tl, c)]
    if not chips:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(tl, c))
               for c in chips) / len(chips)


def idle_share(tl: Timeline) -> float:
    return 1.0 - busy_s(tl) / tl.window_s


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name)


def in_window(tl: Timeline, ev: Event) -> bool:
    return tl.window[0] <= ev.start and ev.end <= tl.window[1]


def roles(tl: Timeline) -> Dict[str, str]:
    """Program name -> the host span most of its executions were enqueued
    in (``NO_SPAN`` where none was open)."""
    if tl._roles is None:
        votes: Dict[str, Dict[str, int]] = defaultdict(
            lambda: defaultdict(int))
        for evs in tl.modules.values():
            for e in evs:
                t = tl.enqueued.get(str(e.stats.get("run_id")))
                if t is not None:
                    votes[e.name][attribute(tl, t)] += 1
        tl._roles = {name: max(v.items(), key=lambda kv: kv[1])[0]
                     for name, v in votes.items()}
    return tl._roles


def module_events(tl: Timeline, role: str) -> List[Event]:
    """Executions, wholly inside the window, of every program whose role
    is ``role``, over all chips."""
    r = roles(tl)
    return [e for evs in tl.modules.values() for e in evs
            if r.get(e.name) == role and in_window(tl, e)]


def module_seconds(tl: Timeline, role: str) -> float:
    return sum(e.dur for e in module_events(tl, role))


def host_spans(tl: Timeline, name: str) -> List[Event]:
    """Annotated host spans called ``name`` that ended inside the window."""
    return [e for e in tl.host
            if e.name == name and tl.window[0] <= e.end <= tl.window[1]]


def program_spans(tl: Timeline, name: str) -> List[Event]:
    """The program's spans called ``name`` (``alise.`` dropped) that ended
    inside the window."""
    return [e for e in tl._named.get(name, ())
            if tl.window[0] <= e.end <= tl.window[1]]


def within(tl: Timeline, outer: Event, name: str) -> List[Event]:
    """The program's spans called ``name`` that lie inside the span
    ``outer``, on its thread."""
    evs = tl._named.get(name, [])
    i = bisect.bisect_left(tl._named_starts.get(name, []), outer.start)
    out = []
    for e in evs[i:]:
        if e.start > outer.end:
            break
        if e.end <= outer.end and e.thread == outer.thread:
            out.append(e)
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def top_ops(tl: Timeline, n: int = 10) -> List[list]:
    """Device seconds per (program role, op), the largest ``n``, over all
    chips and inside the window.  An op that contains the next one (the
    loop over layers) is left out: its body's ops carry its time."""
    lo, hi = tl.window
    role = roles(tl)
    total: Dict[str, float] = defaultdict(float)
    for chip, ops in tl.ops.items():
        mods = sorted(tl.modules.get(chip, []), key=lambda e: e.start)
        starts = [m.start for m in mods]
        ops = sorted(ops, key=lambda e: e.start)
        for k, op in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1].start < op.end:
                continue
            iv = _clip(op, lo, hi)
            if not iv:
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            mod = "?"
            if i >= 0 and op.start < mods[i].end:
                mod = role.get(mods[i].name) or module_name(mods[i].name)
            total[f"{mod}/{op_name(op.name)}"] += iv[1] - iv[0]
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tl: Timeline) -> List[Tuple[float, float]]:
    """Intervals of the window in which no chip ran an op."""
    lo, hi = tl.window
    busy = union([iv for c in set(tl.ops) | set(tl.modules)
                  for iv in busy_intervals(tl, c)])
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(tl: Timeline, t: float) -> str:
    """The innermost annotated host span open at time ``t``: of the spans
    that contain it, the one that started last."""
    i = bisect.bisect_right(tl._starts, t) - 1
    while i >= 0 and tl._reach[i] > t:
        if tl._spans[i].end > t:
            return tl._spans[i].name
        i -= 1
    return NO_SPAN


def idle_by_host_span(tl: Timeline, n: int = 10) -> List[list]:
    """Idle seconds of the device summed by the host span open at each
    gap's middle, the largest ``n``."""
    total: Dict[str, float] = defaultdict(float)
    for a, b in idle_gaps(tl):
        total[attribute(tl, (a + b) / 2)] += b - a
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------------------ loading
def _events(line, thread: int = 0) -> List[Event]:
    out = []
    with warnings.catch_warnings():     # the stats type lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for e in line.events:
            out.append(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                             {k: v for k, v in e.stats}, thread))
    return out


def load(path: str) -> Timeline:
    """Read an ``.xplane.pb`` file into a :class:`Timeline`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    program: List[Event] = []
    enqueued: Dict[str, float] = {}
    dispatched: Dict[str, float] = {}       # flow id -> dispatch time
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
        elif plane.name == "/host:CPU":
            for thread, line in enumerate(plane.lines):
                issue: List[Event] = []
                for e in _events(line, thread):
                    if e.name.startswith(PREFIX):
                        e.name = e.name[len(PREFIX):]
                        host.append(e)
                    elif e.name.startswith(PROGRAM):
                        e.name = e.name[len(PROGRAM):]
                        program.append(e)
                    elif e.name == "tpu::System::Execute" and "_p" in e.stats:
                        dispatched[str(e.stats["_p"])] = e.start
                    elif e.name.endswith("=>IssueSequencedEvent"):
                        issue.append(e)
                    elif e.name == "DoEnqueueProgram" and "run_id" in e.stats:
                        flow = next((str(i.stats.get("_c")) for i in issue
                                     if i.start <= e.start <= i.end), None)
                        enqueued[str(e.stats["run_id"])] = (flow, e.start)
    enqueued = {rid: dispatched.get(flow, t)
                for rid, (flow, t) in enqueued.items()}
    windows = [e for e in host if e.name == WINDOW[len(PREFIX):]]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} span; the traced window "
                         "is unknown")
    w = max(windows, key=lambda e: e.dur)
    return Timeline((w.start, w.end), ops, modules, host, enqueued,
                    program)
