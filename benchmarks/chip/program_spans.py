"""The program's own host spans in the traced window, on the profiler's clock.

The program opens a ``repro.obs`` span ``<entry>.call`` around each call of an
estimator-picked entry point (``stencil25.call``, ``lbm_step.call``), and
JAX's profiler records it on the host plane beside the benchmark's
``window`` annotation.  :func:`of` reads the window's trace once per run and
keeps the calls that lie inside the window and the device's idle intervals
there, for the metrics that attribute idle time to host dispatch.  A trace
without such spans, as from a program that opens none, gives ``None``.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import trace_reduce

TRACES = Path(__file__).resolve().parent / ".traces"  # bench.py's trace directory
WINDOW = "window"
CALL_SUFFIX = ".call"


@dataclass
class ProgramSpans:
    calls: list[trace_reduce.Span]  # the ``<entry>.call`` spans inside the window
    idle: dict[str, list[tuple[float, float]]]  # the window's device-idle intervals per chip


def load(run) -> trace_reduce.Trace | None:
    """The run's window trace from ``.traces/<cell>/window/``, keeping the
    ``window`` and ``*.call`` host spans; ``None`` where there is none."""
    found = sorted((TRACES / run.cell.name / "window").glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        return None
    trace = trace_reduce.load(found[0])
    trace.host_spans = [s for s in trace.host_spans if s.name == WINDOW or s.name.endswith(CALL_SUFFIX)]
    return trace


def reduce(trace: trace_reduce.Trace, seconds: float) -> ProgramSpans | None:
    """The calls and idle intervals of the first ``window`` span, which must
    last ``seconds`` (the run's own window); ``None`` without such a window,
    a device plane or any call."""
    windows = [s for s in trace.host_spans if s.name == WINDOW]
    if not windows or not trace.device_ops:
        return None
    w = windows[0]
    if abs((w.end - w.start) - seconds) > 1e-9:
        return None
    calls = [s for s in trace.host_spans
             if s.name.endswith(CALL_SUFFIX) and w.start <= s.start and s.end <= w.end]
    if not calls:
        return None
    idle = {chip: trace_reduce.gaps(trace_reduce.union(trace_reduce.clip(ops, w.start, w.end)),
                                    w.start, w.end)
            for chip, ops in trace.device_ops.items()}
    return ProgramSpans(calls, idle)


_last: tuple[object, ProgramSpans | None] = (None, None)


def of(run) -> ProgramSpans | None:
    """:func:`reduce` of :func:`load`, read once per run; ``None`` for an
    untraced run."""
    global _last
    if run.trace is None:
        return None
    if _last[0] is not run:
        trace = load(run)
        _last = (run, None if trace is None else reduce(trace, run.trace.seconds))
    return _last[1]


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Total length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
