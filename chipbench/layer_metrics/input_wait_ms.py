"""Median time the loop's thread waited in ``Prefetcher.__next__`` for a
batch (the library's ``input.wait`` span, round its queue's ``get``) over the
measured window's steps. The harness's ``data_wait_ms`` times the same steps
from outside, so this cannot be longer."""
from chipbench import spans

UNIT = "ms"
LAYER = "input"
MOVES = "samples_per_s_chip"


def applies(cell):
    return spans.available()


def read(run):
    return spans.median_ms(spans.aligned(
        "input.wait", spans.seconds(spans.window(run, "input.wait")),
        run.waits, "data_wait_ms"))
