"""Device time a step in operations whose scope path in the step program's
own map lies under ``hvd_kda`` (``chipbench/kda_scopes.py``), forward,
backward and recomputed together, Mosaic kernels left out: a delta layer's
projections, taps, norms, gates and the delta rule itself."""
from chipbench import kda_scopes

UNIT = "ms"
LAYER = "model"
MOVES = "samples_per_s_chip"
applies = kda_scopes.applies


def read(run):
    return kda_scopes.of(run)["hvd_kda"]
