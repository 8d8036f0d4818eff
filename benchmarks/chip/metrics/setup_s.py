"""Process start to the first timed step: device init, fields from the seed,
the estimator's pick, compile or cache load, and two warm-up steps."""


def read(run):
    return run.setup_s
