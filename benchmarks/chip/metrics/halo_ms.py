"""Device time per step per chip of every op of a two-kernel step that is
neither Pallas kernel (``kernel_pattern`` nor ``phase_kernel_pattern``): on a
domain split over chips, the seam exchange's collective-permutes, the slices
of the rows each chip sends, and whatever else the jitted entry point runs.
Summed over the chips' device planes, over the cell's chips and the steps."""
from trace_reduce import matching


def read(run):
    spec = run.cell.spec
    if run.trace is None or "phase_kernel_pattern" not in spec:
        return None
    ops = run.trace.op_seconds
    other = sum(ops.values()) - run.kernel_seconds() - matching(ops, spec["phase_kernel_pattern"])
    return other / run.cell.chips / run.steps * 1e3
