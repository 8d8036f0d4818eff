"""Share of the traced window's device-idle time that lies inside the
program's ``<entry>.call`` spans, in percent: idle time the host spends
dispatching calls.  The rest is the benchmark's own loop, collection pauses
or waiting."""
import program_spans
import trace_reduce


def read(run):
    spans = program_spans.of(run)
    if spans is None:
        return None
    idle = sum(b - a for gaps in spans.idle.values() for a, b in gaps)
    if idle <= 0:
        return None
    calls = trace_reduce.union(spans.calls)
    return sum(program_spans.overlap(gaps, calls) for gaps in spans.idle.values()) / idle * 100.0
