"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, the time
of each device op, and the idle gaps between them.

The trace holds one plane per TPU chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per executed HLO op, a Pallas kernel among them, and host
planes whose lines carry the benchmark's own ``jax.profiler.TraceAnnotation``
spans.  Both are on the profiler's one clock, so a host span can bound a
stretch of device time and name what the host did in a device gap.

An op event's name is the HLO instruction's whole text; :func:`short_name`
cuts it to the instruction's name, opcode and result shape.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"


def short_name(hlo: str) -> str:
    """``%stencil25.1 = f32[8,512]{1,0:T(8,128)} custom-call(...), ...`` ->
    ``%stencil25.1 custom-call f32[8,512]``; other names are kept, cut to 160."""
    text = re.sub(r"\{[^{}]*\}", "", hlo)
    m = re.match(r"^(%\S+) = (.*?) ?([\w-]+)\(", text)
    return f"{m[1]} {m[3]} {m[2]}" if m else hlo[:160]


@dataclass(frozen=True)
class Span:
    name: str
    start: float  # seconds on the trace's clock
    end: float


@dataclass
class Trace:
    """Device op events per chip and the benchmark's host spans."""

    device_ops: dict[str, list[Span]] = field(default_factory=dict)
    host_spans: list[Span] = field(default_factory=list)


def load(path: str, host_names: set[str] | None = None) -> Trace:
    """Read ``path``; keep every device op and the host spans whose name is in
    ``host_names`` (all host spans when ``None``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    trace = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Span(short_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events]
            trace.device_ops[plane.name] = sorted(ops, key=lambda s: s.start)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if host_names is None or e.name in host_names:
                        trace.host_spans.append(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    trace.host_spans.sort(key=lambda s: s.start)
    return trace


def clip(spans: list[Span], start: float, end: float) -> list[Span]:
    """The parts of ``spans`` that lie in [start, end]."""
    return [Span(s.name, max(s.start, start), min(s.end, end))
            for s in spans if s.end > start and s.start < end]


def union(spans: list[Span]) -> list[tuple[float, float]]:
    """Merge overlapping spans into disjoint, sorted intervals."""
    merged: list[list[float]] = []
    for s in sorted(spans, key=lambda s: s.start):
        if merged and s.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s.end)
        else:
            merged.append([s.start, s.end])
    return [(a, b) for a, b in merged]


def gaps(busy: list[tuple[float, float]], start: float, end: float) -> list[tuple[float, float]]:
    """The idle intervals of [start, end] that ``busy`` (disjoint, sorted) leaves."""
    out, t = [], start
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if end > t:
        out.append((t, end))
    return out


def host_activity(spans: list[Span], t: float) -> str:
    """Name of the innermost (latest-starting) host span open at ``t``."""
    name, started = "none", float("-inf")
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and s.start >= started:
            name, started = s.name, s.start
    return name


@dataclass
class Window:
    """What happened on the device during one host span (the traced window)."""

    seconds: float
    busy_s: float  # union of op intervals, averaged over chips
    op_seconds: dict[str, float]  # summed device time by op name, all chips
    op_counts: dict[str, int]
    # (host activity at the gap's midpoint, seconds, start from the window's), longest first
    idle_gaps: list[tuple[str, float, float]]


def window(trace: Trace, span_name: str, top: int = 10) -> Window:
    """Reduce ``trace`` over the first host span called ``span_name``."""
    spans = [s for s in trace.host_spans if s.name == span_name]
    if not spans:
        raise ValueError(f"trace has no host span {span_name!r}")
    if not trace.device_ops:
        raise ValueError("trace has no TPU device plane")
    w = spans[0]
    busy_total, op_seconds, op_counts, all_gaps = 0.0, defaultdict(float), defaultdict(int), []
    inner = [s for s in trace.host_spans if s is not w and w.start <= s.start <= w.end]
    for ops in trace.device_ops.values():
        ops = clip(ops, w.start, w.end)
        for s in ops:
            op_seconds[s.name] += s.end - s.start
            op_counts[s.name] += 1
        busy = union(ops)
        busy_total += sum(b - a for a, b in busy)
        all_gaps += gaps(busy, w.start, w.end)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return Window(
        seconds=w.end - w.start,
        busy_s=busy_total / len(trace.device_ops),
        op_seconds=dict(op_seconds),
        op_counts=dict(op_counts),
        idle_gaps=[(host_activity(inner, (a + b) / 2), b - a, a - w.start) for a, b in longest],
    )


def matching(op_seconds: dict[str, float], pattern: str) -> float:
    """Device seconds of the ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(t for name, t in op_seconds.items() if rx.search(name))


def top_ops(op_seconds: dict[str, float], top: int = 10) -> list[list]:
    return [[n, t] for n, t in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]]
