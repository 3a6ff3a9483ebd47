"""Device time a step in operations under ``hvd_conv_mix``
(``chipbench/conv_scopes.py``), forward, backward and recomputed together: a
conv layer's two gates and its taps, without the products around them.
What XLA fuses into a product's fusion counts under that product's scope."""
from chipbench import conv_scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"
applies = conv_scopes.applies


def read(run):
    return conv_scopes.of(run)["hvd_conv_mix"]
