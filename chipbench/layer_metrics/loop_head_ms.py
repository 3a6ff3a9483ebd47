"""Device time a step under ``hvd_loop_head`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: a pass's head product, log-sum-exp
and label gather (the ``while`` umbrellas of the loss's loop are dropped)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "looped")


def read(run):
    return scopes.of(run).scope_ms["hvd_loop_head"]
