"""Device time per step of every op of a two-kernel step that is neither
Pallas kernel (``kernel_pattern`` nor ``phase_kernel_pattern``): the x
ghost-pads and whatever else the jitted entry point runs."""
from trace_reduce import matching


def read(run):
    spec = run.cell.spec
    if run.trace is None or "phase_kernel_pattern" not in spec:
        return None
    ops = run.trace.op_seconds
    other = sum(ops.values()) - run.kernel_seconds() - matching(ops, spec["phase_kernel_pattern"])
    return other / run.steps * 1e3
