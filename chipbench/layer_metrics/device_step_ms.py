"""Median duration of the step program's executions on device 0 (the
``XLA Modules`` line of the trace)."""
import statistics

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return True


def read(run):
    return statistics.median(run.trace.step_ns) / 1e6
