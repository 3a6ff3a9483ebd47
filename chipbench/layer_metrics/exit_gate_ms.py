"""Device time a step under ``hvd_exit_gate`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: the gate's product, the exit
distribution, its entropy and the weighing."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "looped")


def read(run):
    return scopes.of(run).scope_ms["hvd_exit_gate"]
