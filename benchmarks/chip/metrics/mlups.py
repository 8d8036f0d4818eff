"""Lattice cells updated over the whole window, all domains, per second, in millions."""
import math


def read(run):
    return run.steps * run.domains * math.prod(run.shape) / run.window_s / 1e6
