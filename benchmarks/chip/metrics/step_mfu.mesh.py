"""The whole step's share of the peak of the cell's chips, in percent: the
roofline time of the step's compulsory work on the whole domain (every
domain), at one chip's peak, times the steps, over the traced window times
the number of chips the domain is split over."""
from harness import roofline_s


def read(run):
    if run.trace is None:
        return None
    nbytes, flops = run.work
    return (roofline_s(run.peak, nbytes, flops) * run.domains * run.steps
            / (run.trace.seconds * run.cell.chips) * 100.0)
