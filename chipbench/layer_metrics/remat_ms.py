"""Device time a step in the recomputation inside the backward pass of the step program: the leaf
operations on device 0 whose ``op_name`` in the program's own map
(``chipbench/scopes.py``) 
carries ``rematted_computation``. 0.0 where the recomputed operations
are elementwise and fuse into the backward's fusions (the ``dots`` policy)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.available()


def read(run):
    return scopes.of(run).phase_ms["remat"]
