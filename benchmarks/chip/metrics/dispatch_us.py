"""Host microseconds per call of the configuration's entry point in the traced
window: the mean length of the program's ``<entry>.call`` spans, each the
host side of one jitted call."""
import program_spans


def read(run):
    spans = program_spans.of(run)
    if spans is None:
        return None
    return sum(c.end - c.start for c in spans.calls) / len(spans.calls) * 1e6
