"""Median host time inside the ``step(...)`` call over the measured window's
steps: argument handling and launch. Hidden behind the device while it is
shorter than the device's step."""
import statistics

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return True


def read(run):
    return 1e3 * statistics.median(run.dispatches)
