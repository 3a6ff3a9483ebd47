"""Device time a step under ``hvd_moe_route`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: the router's scores, the top-k, the sort of the
(token, choice) pairs and the counts."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "latent_moe", "window_moe")


def read(run):
    return scopes.of(run).scope_ms["hvd_moe_route"]
