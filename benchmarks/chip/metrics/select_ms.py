"""Host time of the configuration's own estimator pick (``select_*``) at the
cell's shape."""


def read(run):
    return run.select_s * 1e3 if run.select_s > 0 else None
