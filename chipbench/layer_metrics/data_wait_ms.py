"""Median host time ``next(feed)`` blocked, by the benchmark's clock, over
the measured window's steps: what the step waits for ``data.py``."""
import statistics

UNIT = "ms"
LAYER = "input"
MOVES = "samples_per_s_chip"


def applies(cell):
    return True


def read(run):
    return 1e3 * statistics.median(run.waits)
