"""Median host time of the step's dispatch (the library's ``step.dispatch``
span, round the jitted call inside the step function) over the measured
window's steps. ``host_dispatch_ms`` less this is what the monitor and the
telemetry round the dispatch cost; an earlier line gives it."""
from chipbench import spans

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return spans.available()


def read(run):
    inner = spans.aligned(
        "step.dispatch", spans.seconds(spans.window(run, "step.dispatch")),
        run.dispatches, "host_dispatch_ms")
    run.info(what="dispatch_wrapper", wrapper_ms=spans.median_ms(
        o - i for i, o in zip(inner, run.dispatches)))
    return spans.median_ms(inner)
