"""The program's own names in a traced window: the device scopes
(``jax.named_scope("repro.…")``) each op was traced under, and the host
spans (``TraceAnnotation("repro.loop.…")``) of its training loop.

A scope is metadata the compiler keeps on each op: the trace's XPlane
event metadata carries it as the ``tf_op`` stat (JAX's name stack, e.g.
``jit(run)/while/body/repro.model.layers/while/body/squeeze:``), keyed by
the op's full HLO text — ``trace.Event.text``.  ``ProfileData`` does not
expose event metadata, so ``op_scopes`` walks the protobuf wire format of
the ``.xplane.pb`` for it.  An op belongs to the innermost ``repro.*``
component of its name stack; device time is attributed by self time (an
op's time less the ops nested in it), so the parts add up to busy time.
A metric reader is handed only the ``Context``, which does not name its
trace file: ``of(ctx)`` opens the file where ``run.py`` writes it.

The host spans are host events of ``ctx.trace`` like any other; the
functions below read them from the ``Context`` alone.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from pathlib import Path

from . import bench
from . import trace as tr
from .context import KERNELS

OUT = Path(__file__).resolve().parent / "out"
SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
PROGRAM = "repro."
TF_OP = "tf_op"
D2H = "D2H Dispatch"    # the runtime's host event for one device→host copy


# --- protobuf wire format ---------------------------------------------------

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a message: an int for the
    scalar wire types, a memoryview of the bytes for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _map_values(entries) -> list:
    """The values (field 2) of protobuf map entries."""
    return [v for e in entries for f, v in _fields(e) if f == 2]


def op_scopes(path) -> dict:
    """device index -> {HLO text: tf_op} from the trace's XPlane event
    metadata (XSpace.planes = 1; XPlane name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata name = 2, stats = 5; XStatMetadata
    id = 1, name = 2; XStat metadata_id = 1, str_value = 5, ref_value = 7)."""
    out = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        if f != 1:
            continue
        name, events, stats = "", [], []
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        m = tr.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_names = {}
        for md in _map_values(stats):
            fields = dict(_fields(md))
            stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        tf_op = [k for k, v in stat_names.items() if v == TF_OP]
        scopes = {}
        for md in _map_values(events):
            text, scope = "", None
            for h, v in _fields(md):
                if h == 2:
                    text = bytes(v).decode()
                elif h == 5:
                    stat = dict(_fields(v))
                    if tf_op and stat.get(1) == tf_op[0]:
                        scope = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if scope is not None:
                scopes[text] = scope
        out[int(m.group(1))] = scopes
    return out


def innermost(tf_op: str) -> str:
    """The innermost ``repro.*`` component of a name stack, or ''."""
    found = SCOPE.findall(tf_op)
    return found[-1] if found else ""


# --- device scopes ----------------------------------------------------------

class Program:
    """The program's device scopes in ``ctx``'s window, read from the
    trace at ``path`` (None: no trace found, every reading None)."""

    def __init__(self, ctx, path=None):
        self.ctx = ctx
        self.path = path

    @functools.cached_property
    def parts(self) -> dict:
        """Device seconds in the window, averaged over the chips, by
        owner: a kernel's name for its calls, else the op's innermost
        ``repro.*`` scope, '' for ops outside every scope.  Self times:
        the parts sum to ``ctx.busy_s``."""
        ctx = self.ctx
        scopes = op_scopes(self.path) if self.path else {}
        out: dict = {}
        for i in range(ctx.chips):
            known = scopes.get(i, {})
            events = [dataclasses.replace(
                e, name=e.name if e.name in KERNELS
                else innermost(known.get(e.text, "")))
                for e in ctx.trace.devices.get(i, [])]
            for key, s in tr.self_seconds(events, ctx.t0, ctx.t1).items():
                out[key] = out.get(key, 0.0) + s / ctx.chips
        return out

    def scope_ms_per_step(self, scope: str):
        """Device ms per step of the non-kernel ops whose innermost scope
        is ``scope``; None where no op of the window carries any program
        scope (a program without them, or no trace)."""
        if not any(k.startswith(PROGRAM) for k in self.parts):
            return None
        return 1e3 * self.parts.get(scope, 0.0) / self.ctx.steps


def trace_path(ctx):
    """The run's trace file, where ``run.py`` writes it for the cell whose
    configuration and traffic ``ctx`` holds (``out/trace/<cell>/``, which
    it empties before tracing); None where there is none."""
    for w in bench.load_benchmark()["workloads"]:
        cell = bench.resolve(w["name"])
        if cell.config == ctx.config and cell.traffic == ctx.traffic:
            try:
                return tr.find_trace(OUT / "trace" / w["name"])
            except FileNotFoundError:
                return None
    return None


_last: list = []        # [ctx, its Program]: every reader of a run shares one


def of(ctx) -> Program:
    """The ``Program`` of the run's own trace."""
    if not (_last and _last[0] is ctx):
        _last[:] = [ctx, Program(ctx, trace_path(ctx))]
    return _last[1]


# --- host spans ---------------------------------------------------------

def window_spans(ctx, name: str) -> list:
    """The host events named ``name`` that lie inside the window."""
    return [e for e in ctx.trace.host if e.name == name
            and e.start >= ctx.t0 and e.end <= ctx.t1]


def gap_ms_per_step(ctx, span: str):
    """Device idle ms per step, averaged over the chips, in the idle gaps
    that ``trace.label_gap`` gives to ``span`` (the host event that
    overlaps a gap the most): whole gaps, so the reading does not hang on
    how closely the profiler aligns the device's clock with the host's.
    None where the window holds no such span."""
    if not window_spans(ctx, span):
        return None
    host = [e for e in ctx.trace.host if e.end > ctx.t0 and e.start < ctx.t1
            and e.name != tr.HARNESS + "window"]
    starts = [e.start for e in host]
    reach = max(e.end - e.start for e in host)
    idle = 0.0
    for i in range(ctx.chips):
        for a, b in tr.idle_gaps(ctx.trace.devices.get(i, []),
                                 ctx.t0, ctx.t1):
            near = host[bisect.bisect_left(starts, a - reach):
                        bisect.bisect_left(starts, b)]
            if tr.label_gap(near, a, b) == span:
                idle += b - a
    return 1e3 * idle / ctx.chips / ctx.steps


def span_ms_per_step(ctx, name: str):
    """Host ms per step inside the window's ``name`` spans; None where
    the window holds none."""
    spans = window_spans(ctx, name)
    if not spans:
        return None
    return 1e3 * sum(e.end - e.start for e in spans) / ctx.steps


def events_per_step(ctx, span: str, event: str):
    """Host events named ``event`` that start inside the window's ``span``
    spans, per step; None where the window holds no such span."""
    spans = window_spans(ctx, span)
    if not spans:
        return None
    n = sum(1 for e in ctx.trace.host if e.name == event and any(
        s.start <= e.start < s.end for s in spans))
    return n / ctx.steps
