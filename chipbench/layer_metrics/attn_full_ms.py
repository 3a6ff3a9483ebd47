"""Device time a step under ``hvd_attn_full`` as the innermost model scope in
the step program's own map (``chipbench/scopes.py``), forward, backward
and recomputed together, Mosaic kernels (the library's and XLA's
ragged-dot kernels) left out: a full-attention layer's projections,
rotary, repeat of the KV heads, gate a head and output product."""
from chipbench import scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"


def applies(cell):
    return scopes.family(cell, "window_moe")


def read(run):
    return scopes.of(run).scope_ms["hvd_attn_full"]
