"""The D3Q15 Pallas kernel's (``lbm_step``) share of its roofline inside a
step of two kernels, in percent: the roofline time of the kernel's own
compulsory work (the configuration's ``phase_kernel_bytes_per_cell`` and
``phase_kernel_flops_per_cell``), every call in the traced window, over the
device time of the ops that ``phase_kernel_pattern`` matches there."""
import math

from harness import roofline_s
from trace_reduce import matching


def read(run):
    spec = run.cell.spec
    if run.trace is None or "phase_kernel_pattern" not in spec:
        return None
    t = matching(run.trace.op_seconds, spec["phase_kernel_pattern"])
    if t <= 0:
        return None
    cells = math.prod(run.shape)
    nbytes, flops = cells * spec["phase_kernel_bytes_per_cell"], cells * spec["phase_kernel_flops_per_cell"]
    return roofline_s(run.peak, nbytes, flops) * run.domains * run.steps / t * 100.0
