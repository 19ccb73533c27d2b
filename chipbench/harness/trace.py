"""From a JAX profiler trace to the intervals the layer metrics read.

A trace is reduced once to a small plain structure (``Trace``): for every TPU
the program executions (line "XLA Modules") and the operations inside them
(line "XLA Ops"), and from the host the benchmark's own annotations (names
that start with ``bench.`` or ``ht.``) and the moments the runtime issued a
program (``tpu::System::Execute``).  All times are nanoseconds on the
profiler's clock.  The device's part of that clock ran 1.2 ms ahead of the
host's in the traces of PR 22 (a program seemed to start before the call that
launched it), which is much beside a 0.3 ms eager operation, so ``calibrate``
shifts the device's events until no program starts before it was issued.  The
same structure is what ``tests/benchmark/fixtures`` keeps of a trace recorded
on the chip, so the arithmetic below is tested on what the chip really wrote.

Busy time is the union of the intervals in which an operation runs.  The ops
line nests (a ``while`` spans its body's operations), so times by name are
self times and "another operation" means a leaf.  A collective is an
all-gather, all-to-all, all-reduce, reduce-scatter or collective-permute by
the name XLA gives it; an asynchronous one lasts from its ``-start`` to the
end of its ``-done``.
"""

from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
import statistics
from dataclasses import dataclass, field
from typing import NamedTuple

JOB_SPAN = "bench.job"
HOST_PREFIXES = ("bench.", "ht.")
OUTSIDE = "(no benchmark span)"
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
ISSUED = "tpu::System::Execute"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"^%?(all-gather|all-to-all|all-reduce|reduce-scatter|collective-permute)"
    r"(-start|-done)?\b"
)


class Event(NamedTuple):
    name: str
    start: float
    end: float


@dataclass
class DeviceTrace:
    ordinal: int
    modules: list
    ops: list


@dataclass
class Trace:
    devices: list
    host: list
    issued: list = field(default_factory=list)  # host times at which a program was issued


# ---------------------------------------------------------------------- #
# reading and keeping
# ---------------------------------------------------------------------- #
def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> list:
    """A line's events by start.  A device operation's name is its whole HLO
    instruction; what stands before `` = `` is enough to know it by."""
    if line is None:
        return []
    out = [Event(e.name.split(" = ", 1)[0].lstrip("%"), float(e.start_ns),
                 float(e.start_ns + e.duration_ns)) for e in line.events]
    out.sort(key=lambda e: (e.start, -e.end))
    return out


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices, host, issued = [], [], []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices.append(DeviceTrace(
                int(m.group(1)), _events(lines.get(MODULES_LINE)), _events(lines.get(OPS_LINE))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                host += [e for e in events if e.name.startswith(HOST_PREFIXES)]
                issued += [e.start for e in events if e.name == ISSUED]
    devices.sort(key=lambda d: d.ordinal)
    host.sort(key=lambda e: (e.start, -e.end))
    return Trace(devices, host, sorted(issued))


def clock_lead(trace: Trace):
    """Nanoseconds by which the device's clock is ahead of the host's, or
    ``None`` where the programs cannot be matched with the moments they were
    issued: the least, over all programs, of a program's start on the first
    chip minus the start of the runtime call that issued it (one call per
    chip, the chips of one program issued together)."""
    if not trace.devices or not trace.issued:
        return None
    modules, per_program = trace.devices[0].modules, len(trace.devices)
    if len(trace.issued) != len(modules) * per_program:
        return None
    return min(m.start - h for m, h in zip(modules, trace.issued[::per_program]))


def calibrate(trace: Trace) -> Trace:
    """The trace with the device's events moved onto the host's clock."""
    lead = clock_lead(trace)
    if not lead:
        return trace

    def moved(events):
        return [Event(e.name, e.start - lead, e.end - lead) for e in events]

    return Trace([DeviceTrace(d.ordinal, moved(d.modules), moved(d.ops)) for d in trace.devices],
                 trace.host, trace.issued)


def to_json(trace: Trace) -> dict:
    return {
        "devices": [
            {"ordinal": d.ordinal, "modules": [list(e) for e in d.modules],
             "ops": [list(e) for e in d.ops]}
            for d in trace.devices
        ],
        "host": [list(e) for e in trace.host],
        "issued": trace.issued,
    }


def from_json(obj: dict) -> Trace:
    return Trace(
        [DeviceTrace(d["ordinal"], [Event(*e) for e in d["modules"]], [Event(*e) for e in d["ops"]])
         for d in obj["devices"]],
        [Event(*e) for e in obj["host"]],
        obj.get("issued", []),
    )


def save(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump(to_json(trace), fh, separators=(",", ":"))


def load(path: str) -> Trace:
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return from_json(json.load(fh))


# ---------------------------------------------------------------------- #
# interval arithmetic on sorted, disjoint (start, end) lists
# ---------------------------------------------------------------------- #
def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def intersect(a, b) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list:
    """What of merged ``a`` no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


# ---------------------------------------------------------------------- #
# the traced window and its jobs
# ---------------------------------------------------------------------- #
def jobs(trace: Trace) -> list:
    return [e for e in trace.host if e.name == JOB_SPAN]


def window(trace: Trace) -> tuple:
    spans = jobs(trace)
    if not spans:
        raise ValueError(f"the trace holds no {JOB_SPAN!r} annotation")
    return min(e.start for e in spans), max(e.end for e in spans)


# ---------------------------------------------------------------------- #
# one device
# ---------------------------------------------------------------------- #
def nested(events) -> list:
    """``(event, self_ns, is_leaf)`` for properly nested, start-sorted events."""
    children = [0.0] * len(events)
    has_child = [False] * len(events)
    stack = []
    for i, ev in enumerate(events):
        while stack and events[stack[-1]].end <= ev.start:
            stack.pop()
        if stack:
            children[stack[-1]] += ev.end - ev.start
            has_child[stack[-1]] = True
        stack.append(i)
    return [
        (ev, max(ev.end - ev.start - children[i], 0.0), not has_child[i])
        for i, ev in enumerate(events)
    ]


def leaves(dev: DeviceTrace) -> list:
    return [ev for ev, _, leaf in nested(dev.ops) if leaf]


def busy(dev: DeviceTrace, lo: float, hi: float) -> list:
    events = dev.ops or dev.modules
    return merge(clip([(e.start, e.end) for e in events], lo, hi))


def collective_kind(name: str):
    m = _COLLECTIVE.match(name)
    return (m.group(1), m.group(2)) if m else None


def collectives(dev: DeviceTrace) -> list:
    """``(kind, start, end)`` of every collective, asynchronous pairs joined."""
    out, open_starts = [], collections.defaultdict(collections.deque)
    for ev in leaves(dev):
        kind = collective_kind(ev.name)
        if kind is None:
            continue
        name, phase = kind
        if phase == "-start":
            open_starts[name].append(ev.start)
        elif phase == "-done":
            begun = open_starts[name].popleft() if open_starts[name] else ev.start
            out.append((name, begun, ev.end))
        else:
            out.append((name, ev.start, ev.end))
    return out


def collective_time(dev: DeviceTrace, lo: float, hi: float) -> tuple:
    """``(collective_ns, exposed_ns)`` inside the window: the union of the
    collectives, and the part of it in which no other leaf operation runs."""
    coll = merge(clip([(s, e) for _, s, e in collectives(dev)], lo, hi))
    other = merge(clip(
        [(ev.start, ev.end) for ev in leaves(dev) if collective_kind(ev.name) is None], lo, hi))
    return total(coll), total(subtract(coll, other))


def launches(dev: DeviceTrace, lo: float, hi: float) -> int:
    return sum(1 for m in dev.modules if lo <= m.start < hi)


def launch_gaps(dev: DeviceTrace, spans) -> list:
    """Device-side gaps between one program's end and the next one's start,
    inside each job (a job ends in ``block_until_ready``, so its programs
    lie inside its host span)."""
    gaps = []
    for span in spans:
        inside = [m for m in dev.modules if m.start >= span.start and m.end <= span.end]
        gaps += [max(b.start - a.end, 0.0) for a, b in zip(inside, inside[1:])]
    return gaps


def module_of(dev: DeviceTrace):
    """A function from a time to the name of the program running then."""
    starts = [m.start for m in dev.modules]

    def at(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < dev.modules[i].end:
            return re.sub(r"\(\d+\)$", "", dev.modules[i].name)
        return ""

    return at


# ---------------------------------------------------------------------- #
# the host's side of a gap
# ---------------------------------------------------------------------- #
def host_segments(trace: Trace, lo: float, hi: float) -> list:
    """Disjoint ``(start, end, label)`` covering the window, each labelled
    with the innermost benchmark annotation open then."""
    segs, stack, cursor = [], [], lo

    def emit(until: float) -> None:
        nonlocal cursor
        until = min(until, hi)
        if until > cursor:
            segs.append((cursor, until, stack[-1].name if stack else OUTSIDE))
            cursor = until

    for ev in trace.host:
        if ev.end <= lo or ev.start >= hi:
            continue
        while stack and stack[-1].end <= ev.start:
            emit(stack[-1].end)
            stack.pop()
        emit(ev.start)
        stack.append(ev)
    while stack:
        emit(stack[-1].end)
        stack.pop()
    emit(hi)
    return segs


# ---------------------------------------------------------------------- #
# whole-trace reductions
# ---------------------------------------------------------------------- #
def busy_seconds(trace: Trace) -> tuple:
    """``(mean busy seconds over the devices, window seconds)``."""
    lo, hi = window(trace)
    per_device = [total(busy(d, lo, hi)) for d in trace.devices]
    return statistics.fmean(per_device) / 1e9, (hi - lo) / 1e9


def idle_share(trace: Trace) -> float:
    """1 minus busy over the window, on the chip that idles most."""
    lo, hi = window(trace)
    return max(1.0 - total(busy(d, lo, hi)) / (hi - lo) for d in trace.devices)


def idle_by_label(trace: Trace) -> dict:
    """Idle seconds of the window by what the host was inside, mean over chips."""
    lo, hi = window(trace)
    by_seg = collections.defaultdict(list)
    for s, e, label in host_segments(trace, lo, hi):
        by_seg[label].append((s, e))
    by_label = collections.defaultdict(float)
    for dev in trace.devices:
        idle = subtract([(lo, hi)], busy(dev, lo, hi))
        for label, spans in by_seg.items():
            by_label[label] += total(intersect(idle, spans))
    n = len(trace.devices)
    return {label: ns / n / 1e9 for label, ns in by_label.items() if ns > 0}


def ops_by_name(trace: Trace) -> dict:
    """Self seconds of the window by ``program:operation``, mean over chips."""
    lo, hi = window(trace)
    by_name = collections.defaultdict(float)
    for dev in trace.devices:
        program = module_of(dev)
        for ev, self_ns, _ in nested(dev.ops):
            if lo <= ev.start < hi:
                by_name[f"{program(ev.start)}:{ev.name}".lstrip(":")] += self_ns
    n = len(trace.devices)
    return {name: ns / n / 1e9 for name, ns in by_name.items()}


def breakdown(trace: Trace, top: int = 10) -> dict:
    def largest(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": largest(ops_by_name(trace)), "idle_gaps": largest(idle_by_label(trace))}


def span_ms(trace, name: str):
    """Median milliseconds of the host annotation ``name`` inside the window."""
    if trace is None:
        return None
    lo, hi = window(trace)
    spans = [e.end - e.start for e in trace.host if e.name == name and lo <= e.start < hi]
    return statistics.median(spans) / 1e6 if spans else None
