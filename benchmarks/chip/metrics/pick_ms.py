"""Host milliseconds per estimator pick that an entry point made inside its
jit trace in this process (``estimator.pick_seconds``, the program's own
registry); the benchmark's outside ``select`` is not counted."""

SERIES = "estimator.pick_seconds"


def read(run):
    from repro.obs import metrics

    picks = [h for k, h in metrics.snapshot()["histograms"].items() if k.split("{")[0] == SERIES]
    count = sum(h["count"] for h in picks)
    return sum(h["sum"] for h in picks) / count * 1e3 if count else None
