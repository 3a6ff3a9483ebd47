"""Device time a step in the gradient exchange's unpacking: the leaf operations
on device 0 under ``hvd_exchange_unpack`` in the step program's own map
(``chipbench/scopes.py``): the slices out of the fused
buffers and the casts back."""
from chipbench import scopes

UNIT = "ms"
LAYER = "collectives"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["chips"] > 1 and scopes.available()


def read(run):
    return scopes.of(run).phase_ms["exchange_unpack"]
