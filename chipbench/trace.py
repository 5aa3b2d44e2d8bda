"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the metrics read.

``jax.profiler.ProfileData`` gives planes, lines and events with a start
and a duration in nanoseconds, on one clock for host and device.  Each
TPU is a plane ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event
per executed HLO op, named by the op's HLO text (``%name.N = ...``);
control-flow ops (``while``) contain the ops of their body, so busy time
is a union of intervals, not a sum.  Host planes hold the profiler's own
runtime events and the harness's ``TraceAnnotation`` spans, which are
named ``chipbench.*``.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HARNESS = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    start: float        # seconds on the trace's clock
    end: float
    name: str           # op name without the instance number, or host name
    text: str           # the full event name


@dataclasses.dataclass
class Trace:
    devices: dict       # device index -> [Event] of its XLA ops, by start
    host: list          # host-thread events, by start

    def annotations(self, name: str) -> list:
        return [e for e in self.host if e.name == HARNESS + name]

    def window(self):
        """(start, end) of the harness's traced window."""
        spans = self.annotations("window")
        if not spans:
            raise ValueError("trace holds no chipbench.window span")
        return spans[0].start, spans[0].end


def op_name(text: str) -> str:
    """'%perturbed_matmul_pair.88 = (...) custom-call(...)' → 'perturbed_matmul_pair'."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name != OPS_LINE:
                continue
            evs = [Event(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         op_name(e.name) if m else e.name, e.name)
                   for e in line.events]
            if m:
                devices.setdefault(int(m.group(1)), []).extend(evs)
            elif plane.name.startswith("/host:"):
                host.extend(evs)
    for evs in devices.values():
        evs.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


def find_trace(directory) -> Path:
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _clip(e: Event, t0: float, t1: float):
    return max(e.start, t0), min(e.end, t1)


def busy_intervals(events, t0: float, t1: float) -> list:
    """Union of the events' intervals inside [t0, t1], merged and sorted."""
    out = []
    for e in events:
        a, b = _clip(e, t0, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_seconds(events, t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, t0, t1))


def idle_gaps(events, t0: float, t1: float) -> list:
    """(start, end) of every stretch of [t0, t1] with no op running."""
    gaps, cursor = [], t0
    for a, b in busy_intervals(events, t0, t1):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < t1:
        gaps.append((cursor, t1))
    return gaps


def op_seconds(events, t0: float, t1: float, names) -> tuple:
    """(seconds, calls) of the ops whose name is in ``names``."""
    total, calls = 0.0, 0
    for e in events:
        if e.name in names:
            a, b = _clip(e, t0, t1)
            if b > a:
                total += b - a
                calls += 1
    return total, calls


def self_seconds(events, t0: float, t1: float) -> dict:
    """op name -> seconds it ran itself, not counting the ops nested in it."""
    out: dict = {}
    stack: list = []        # [end, name, start, seconds of nested ops]

    def pop():
        end, name, start, child = stack.pop()
        own = end - start - child
        out[name] = out.get(name, 0.0) + own
        if stack:
            stack[-1][3] += end - start

    for e in events:
        a, b = _clip(e, t0, t1)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            pop()
        stack.append([b, e.name, a, 0.0])
    while stack:
        pop()
    return out


def label_gap(host, a: float, b: float) -> str:
    """What the host was doing in the device gap [a, b]: the host event,
    other than the window span itself, that overlaps it the most."""
    best, best_overlap = "unlabelled", 0.0
    for e in host:
        if e.start >= b:
            break
        if e.name == HARNESS + "window":
            continue
        overlap = min(e.end, b) - max(e.start, a)
        if overlap > best_overlap:
            best, best_overlap = e.name, overlap
    return best
