"""Device time a step in the gradient exchange's packing: the leaf operations
on device 0 under ``hvd_exchange_pack`` in the step program's own map
(``chipbench/scopes.py``): the casts and concatenations into the
fused buffers."""
from chipbench import scopes

UNIT = "ms"
LAYER = "collectives"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["chips"] > 1 and scopes.available()


def read(run):
    return scopes.of(run).phase_ms["exchange_pack"]
