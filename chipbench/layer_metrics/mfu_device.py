"""Model FLOP/s utilisation of the device while it runs the step: the
operations forward and backward REQUIRE for a step (the family's shape
function; recomputation not counted) over the device's median step time,
the chips, and the published peak."""
import statistics

UNIT = "%"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return True


def read(run):
    step_s = statistics.median(run.trace.step_ns) / 1e9
    return 100 * run.task.flops_per_step / step_s / (
        run.cell["chips"] * run.peaks["flops_per_s"])
