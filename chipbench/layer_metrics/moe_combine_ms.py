"""Device time a step under ``hvd_moe_combine`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: the gate-weighed sum of the experts' rows back
into their tokens, and the gate gradient."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "latent_moe", "window_moe")


def read(run):
    return scopes.of(run).scope_ms["hvd_moe_combine"]
