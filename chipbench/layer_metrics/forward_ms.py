"""Device time a step in the forward pass of the step program: the leaf
operations on device 0 whose ``op_name`` in the program's own map
(``chipbench/scopes.py``) carries ``jvp(hvd_forward)`` and neither
``transpose(`` nor the recomputation's marker, the flash forward kernels
among them. A fusion counts under its root's scope."""
from chipbench import scopes

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.available()


def read(run):
    return scopes.of(run).phase_ms["forward"]
