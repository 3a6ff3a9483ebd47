"""Device time a step under ``hvd_mla`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: latent attention's projections, norms,
rotary embedding, concatenations and output product (``flash_fwd_ms`` and
``flash_bwd_ms`` read the kernels)."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "latent_moe")


def read(run):
    return scopes.of(run).scope_ms["hvd_mla"]
