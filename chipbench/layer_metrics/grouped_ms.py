"""Device time a step in Mosaic kernels the library did not name: XLA's own
``ragged-dot-none`` kernels (the grouped products of the routed experts) and
their ``ragged-dot-metadata`` calls, told from the ``hvd_flash_*`` kernels by
the instruction name in the step program's own map (``chipbench/scopes.py``).
What ``flash_ms`` counts beyond the flash kernels."""
from chipbench import scopes

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "latent_moe", "window_moe")


def read(run):
    return scopes.of(run).grouped_ms
