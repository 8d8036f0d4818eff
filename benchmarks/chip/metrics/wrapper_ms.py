"""Device time per step of every op that is not the Pallas kernel: the ops
wrapper's x ghost-pad and whatever else the jitted entry point runs."""


def read(run):
    if run.trace is None:
        return None
    other = sum(run.trace.op_seconds.values()) - run.kernel_seconds()
    return other / run.steps * 1e3
