"""The pick's kernel device time per call over the least such time of every
candidate block the compiler accepts, each read from the traced ladder."""


def read(run):
    if not run.ladder or run.pick not in run.ladder:
        return None
    return run.ladder[run.pick] / min(run.ladder.values())
