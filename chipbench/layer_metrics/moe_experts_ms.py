"""Device time a step under ``hvd_moe_experts`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: XLA's part of the routed experts (activation,
masks, sums of cotangents); the grouped products are ``grouped_ms``."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "latent_moe", "window_moe")


def read(run):
    return scopes.of(run).scope_ms["hvd_moe_experts"]
