"""The lbm_d3q27 Pallas kernel's share of its roofline, in percent: the
roofline time of the kernel's own compulsory work (the configuration's
``kernel_bytes_per_cell`` and ``kernel_flops_per_cell``: g in and out, the
new phase in, the velocity in and out), every call in the traced window,
over the kernel's device time there."""
import math

from harness import roofline_s


def kernel_work(spec: dict, shape) -> tuple[float, float]:
    """Compulsory (bytes, FLOPs) of one call of the kernel on one domain."""
    cells = math.prod(shape)
    return float(cells * spec["kernel_bytes_per_cell"]), float(cells * spec["kernel_flops_per_cell"])


def read(run):
    if run.trace is None or run.cell.spec["kernel"] != "lbm_d3q27":
        return None
    t = run.kernel_seconds()
    if t <= 0:
        return None
    nbytes, flops = kernel_work(run.cell.spec, run.shape)
    return roofline_s(run.peak, nbytes, flops) * run.domains * run.steps / t * 100.0
