"""What every estimator-picked kernel entry point shares: a jitted body under
the entry's own name, a host span around each call, a timed pick, and the
chip it picks and compiles for.

Spans (``repro.obs.trace``) land in a running ``jax.profiler`` session, so a
device trace shows each call's host dispatch as ``<entry>.call`` and, where
the body is traced, the estimator's pick as ``<entry>.pick``.  The pick runs
before any steady window, so its duration also goes to the always-on
registry as ``estimator.pick_seconds{entry=<entry>}``.
"""
from __future__ import annotations

import functools

import jax

from ..core.machine import TPUMachine, device_machine
from ..obs import metrics, trace


def entry_point(**jit_kwargs):
    """Decorator: jit ``fn`` under its own name (the HLO module stays
    ``jit_<name>``) and return a thin entry with the same signature that
    opens the span ``<name>.call`` around each call.  ``lower`` and ``trace``
    are the jitted function's.

    Where nothing records spans the entry calls the jitted function bare: at
    ~200 us of host dispatch a call, a span object and its annotation cost
    ~15 us on a TPU v5e host, where a span's parts alone take ~2 us in a
    tight loop."""

    def wrap(fn):
        jitted = jax.jit(fn, **jit_kwargs)
        span_name = f"{fn.__name__}.call"

        @functools.wraps(fn)
        def entry(*args, **kwargs):
            if not trace.recording():
                return jitted(*args, **kwargs)
            with trace.span(span_name):
                return jitted(*args, **kwargs)

        entry.lower = jitted.lower
        entry.trace = jitted.trace
        return entry

    return wrap


def timed_pick(entry: str, select, *args, **kwargs):
    """``select(*args, **kwargs)`` inside the span ``<entry>.pick``, its
    duration observed into ``estimator.pick_seconds{entry=<entry>}``."""
    with trace.span(f"{entry}.pick") as sp:
        out = select(*args, **kwargs)
    metrics.histogram("estimator.pick_seconds", entry=entry).observe(sp.duration_s)
    return out


def chip(interpret: bool) -> tuple[TPUMachine | None, int | None]:
    """``(machine, vmem_limit_bytes)`` an entry picks for and compiles under:
    the chip JAX runs on and its ``vmem_usable``, or ``(None, None)`` in
    interpret mode, which runs on no chip (so its caller names the block)."""
    if interpret:
        return None, None
    machine = device_machine()
    return machine, machine.vmem_usable
