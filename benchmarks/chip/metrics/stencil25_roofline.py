"""The stencil25 Pallas kernel's share of its roofline, in percent."""
from harness import kernel_roofline


def read(run):
    return kernel_roofline(run, "stencil25")
