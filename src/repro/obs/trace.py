"""Structured tracing for the estimation pipeline (`repro.obs` pillar 1).

The paper opens the *hardware's* black box; this module opens the *pipeline's*:
every phase of a sweep (enumerate → IR-trace → prune → estimate batches →
store append → pareto) runs inside a nestable :func:`span`, and an enabled
:class:`Tracer` exports the result as Chrome-trace/Perfetto JSON
(``chrome://tracing`` or https://ui.perfetto.dev load it directly), so the
phase structure of a run is visually inspectable instead of inferred from one
wall-clock number.

Design constraints, in order:

* **Near-zero overhead when disabled.**  Tracing is off by default; a disabled
  :func:`span` is one small-object allocation plus two ``perf_counter`` calls
  (the duration is still measured, because ``SweepStats.wall_s`` is defined as
  the duration of the sweep's span — the trace and the stats agree by
  construction), and, once JAX's profiler is loaded, one ``is_enabled`` check
  of it.  Spans are phase/batch granular, never per-config, so the
  disabled cost on a full sweep is well under the 2% budget
  (``tests/test_obs.py`` asserts it).
* **Process-pool aggregation.**  Pool workers cannot append to the parent's
  tracer.  A worker calls :func:`enable` locally, runs its chunk, and ships
  :func:`export_events` back with its results; the parent's
  :meth:`Tracer.absorb` re-bases the worker's timestamps onto the parent
  timeline via the wall-clock epochs both sides record.  Worker events keep
  their own ``pid``, so Perfetto shows one lane per worker process.
* **Zero dependencies.**  Stdlib only; importable from every layer (frontend,
  core, explore) without cycles.
* **One span, two sinks.**  Once ``jax.profiler`` has been imported by someone
  else, each span opened while a profiler session traces also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the session records
  it on the host plane of its trace, on the same clock as the device ops.  The annotation carries the
  name alone (attributes stay on the Chrome-trace event): the profiler folds
  keyword metadata into the event name, and trace readers match by name.

Usage::

    from repro.obs import trace

    tracer = trace.enable()
    with trace.span("estimate.batch", size=32) as sp:
        ...
        sp.set(cache_hits=7)          # attach attributes mid-span
    tracer.export("trace.json")       # Chrome-trace JSON
    trace.disable()
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any

__all__ = [
    "Span",
    "Tracer",
    "active",
    "disable",
    "enable",
    "export_events",
    "recording",
    "span",
    "validate_chrome_trace",
]

# process-global tracer; None = disabled (the common case, checked per span)
_tracer: Tracer | None = None
_lock = threading.Lock()
# jax.profiler.TraceAnnotation, once some other layer has imported JAX's profiler
_annotation_class = None


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is tracing,
    else None.  Never imports JAX."""
    global _annotation_class
    if _annotation_class is None:
        _annotation_class = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
        if _annotation_class is None:
            return None
    return _annotation_class if _annotation_class.is_enabled() else None


class Span:
    """One timed region.  Always measures its duration (``duration_s`` after
    exit); records a Chrome-trace event only when a tracer is enabled, and a
    profiler event only while a JAX profiler session is tracing."""

    __slots__ = ("name", "args", "t0", "duration_s", "_tracer", "_annotation")

    def __init__(self, name: str, tracer: Tracer | None, args: dict):
        self.name = name
        self.args = args
        self._tracer = tracer
        self._annotation = None
        self.duration_s = 0.0
        self.t0 = 0.0

    def set(self, **attrs: Any) -> None:
        """Attach attributes/counters to the span (shown in the trace UI)."""
        self.args.update(attrs)

    def __enter__(self) -> Span:
        annotation = _profiler_annotation()
        if annotation is not None:
            self._annotation = annotation(self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.duration_s = t1 - self.t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        if self._tracer is not None:
            self._tracer._record(self.name, self.t0, self.duration_s, self.args)


class Tracer:
    """Collects span events; exports/absorbs Chrome-trace JSON.

    Timestamps are microseconds relative to the tracer's epoch; the wall-clock
    epoch recorded alongside lets events from *other processes* (pool workers)
    be re-based onto this timeline in :meth:`absorb`.
    """

    def __init__(self):
        self.epoch_perf = time.perf_counter()
        self.epoch_wall = time.time()
        self.pid = os.getpid()
        self.events: list[dict] = []
        self._elock = threading.Lock()

    def _record(self, name: str, t0: float, dur_s: float, args: dict) -> None:
        ev = {
            "name": name,
            "ph": "X",  # complete event: ts + dur (begin/end implicitly balanced)
            "ts": (t0 - self.epoch_perf) * 1e6,
            "dur": dur_s * 1e6,
            "pid": self.pid,
            "tid": threading.get_ident() & 0xFFFF,
        }
        if args:
            ev["args"] = dict(args)
        with self._elock:
            self.events.append(ev)

    def absorb(self, payload: dict) -> None:
        """Merge :func:`export_events` output from another process, shifting its
        timestamps by the wall-clock epoch difference so both timelines align."""
        shift_us = (payload["epoch_wall"] - self.epoch_wall) * 1e6
        with self._elock:
            for ev in payload["events"]:
                ev = dict(ev)
                ev["ts"] = ev.get("ts", 0.0) + shift_us
                self.events.append(ev)

    def to_chrome(self) -> dict:
        """The full Chrome-trace JSON object (lists every pid as a process)."""
        pids = sorted({ev.get("pid", self.pid) for ev in self.events})
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "ts": 0.0,
                "pid": pid,
                "tid": 0,
                "args": {
                    "name": "repro.estimation"
                    if pid == self.pid
                    else f"repro.worker[{pid}]"
                },
            }
            for pid in pids
        ]
        return {"traceEvents": meta + list(self.events), "displayTimeUnit": "ms"}

    def export(self, path) -> int:
        """Write Chrome-trace JSON to ``path``; returns the event count."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return len(doc["traceEvents"])

    def span_names(self) -> set[str]:
        return {ev["name"] for ev in self.events if ev.get("ph") == "X"}


def enable() -> Tracer:
    """Turn tracing on (idempotent: an already-enabled tracer is returned)."""
    global _tracer
    with _lock:
        if _tracer is None:
            _tracer = Tracer()
        return _tracer


def disable() -> None:
    """Turn tracing off; subsequent spans are duration-only timers again."""
    global _tracer
    with _lock:
        _tracer = None


def active() -> Tracer | None:
    """The enabled tracer, or None when tracing is off."""
    return _tracer


def recording() -> bool:
    """Whether a span opened now is recorded anywhere: by the enabled tracer
    or by a JAX profiler session.  A hot caller that needs no duration skips
    the span when it is not."""
    return _tracer is not None or _profiler_annotation() is not None


def span(name: str, **args: Any) -> Span:
    """A nestable timed region; context-manager.  Cheap when tracing is off."""
    return Span(name, _tracer, args)


def export_events() -> dict:
    """Picklable event payload for cross-process aggregation (pool workers ship
    this back with their results; the parent calls :meth:`Tracer.absorb`)."""
    t = _tracer
    if t is None:
        return {"epoch_wall": time.time(), "events": []}
    with t._elock:
        return {"epoch_wall": t.epoch_wall, "events": [dict(e) for e in t.events]}


def validate_chrome_trace(doc: dict) -> list[str]:
    """Schema check for an exported trace: returns a list of problems (empty =
    valid).  Used by the CI smoke job and ``tests/test_obs.py``.

    Checks: top-level ``traceEvents`` list; every event carries ``ph``, ``ts``
    and ``name``; complete (``X``) events have a non-negative ``dur``; explicit
    begin/end (``B``/``E``) events balance per ``(pid, tid)``.
    """
    problems: list[str] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    depth: dict[tuple, int] = {}
    for i, ev in enumerate(events):
        for fld in ("ph", "ts", "name"):
            if fld not in ev:
                problems.append(f"event {i} missing {fld!r}: {ev}")
        ph = ev.get("ph")
        if ph == "X" and ev.get("dur", -1) < 0:
            problems.append(f"event {i} ({ev.get('name')}): X event without dur >= 0")
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            depth[key] = depth.get(key, 0) + 1
        elif ph == "E":
            depth[key] = depth.get(key, 0) - 1
            if depth[key] < 0:
                problems.append(f"event {i}: E without matching B on {key}")
    for key, d in depth.items():
        if d != 0:
            problems.append(f"unbalanced B/E spans on {key}: depth {d} at end")
    return problems
