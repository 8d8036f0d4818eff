"""Share of the traced window in which no op ran on the device, in percent."""


def read(run):
    if run.trace is None:
        return None
    return (1.0 - run.trace.busy_s / run.trace.seconds) * 100.0
