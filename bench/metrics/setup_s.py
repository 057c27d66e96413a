"""Seconds from process start to the first request due: imports, reaching
the chip, weights, ``deploy()`` and the warm-up that compiles (or loads
from the cache) every shape the window uses."""


def read(run):
    return run.setup_s
