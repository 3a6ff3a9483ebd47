"""Device time a step in the backward pass of the step program: the leaf
operations on device 0 whose ``op_name`` in the program's own map
(``chipbench/scopes.py``) carries
``transpose(jvp(hvd_forward))`` and not the recomputation's marker: the
backward kernels, and whatever of the update XLA fused into a backward
fusion (its root's scope decides)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.available()


def read(run):
    return scopes.of(run).phase_ms["backward"]
