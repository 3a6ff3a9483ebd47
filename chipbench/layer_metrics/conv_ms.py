"""Device time a step in operations whose scope path in the step program's
own map lies under ``hvd_short_conv`` (``chipbench/conv_scopes.py``),
forward, backward and recomputed together, Mosaic kernels left out: a conv
layer's input and output products, its gates and its taps."""
from chipbench import conv_scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"
applies = conv_scopes.applies


def read(run):
    return conv_scopes.of(run)["hvd_short_conv"]
