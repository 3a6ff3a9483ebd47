"""Device time a step in operations under ``hvd_kda_scan``
(``chipbench/kda_scopes.py``), forward, backward and recomputed together:
the chunked delta rule, its chunks' interior, its scans over the chunks and
their loop bodies, without the projections around it. What XLA fuses into
an operation outside the scope counts under that operation's scope."""
from chipbench import kda_scopes

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"
applies = kda_scopes.applies


def read(run):
    return kda_scopes.of(run)["hvd_kda_scan"]
