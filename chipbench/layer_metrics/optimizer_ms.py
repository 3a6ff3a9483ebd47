"""Device time a step in the update of the step program: the leaf
operations on device 0 whose ``op_name`` in the program's own map
(``chipbench/scopes.py``) carries ``hvd_optimizer``. Under-reads
where XLA fuses the update into the backward fusion that makes the
gradient (one chip); ``fused_across_phases_ms`` on the ``program_map`` info
line says by how much."""
from chipbench import scopes

UNIT = "ms"
LAYER = "step program"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.available()


def read(run):
    return scopes.of(run).phase_ms["optimizer"]
