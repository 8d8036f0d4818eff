"""The whole step's share of the chip's peak, in percent: the roofline time of
the step's compulsory work (every domain), times the steps, over the traced
window.  It bounds the kernel's roofline share from below whatever runs in
the step."""
from harness import roofline_s


def read(run):
    if run.trace is None:
        return None
    nbytes, flops = run.work
    return roofline_s(run.peak, nbytes, flops) * run.domains * run.steps / run.trace.seconds * 100.0
