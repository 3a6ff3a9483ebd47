"""Device time a step spends in the Mosaic (Pallas) kernels: the leaf
operations on device 0 whose HLO carries ``tpu_custom_call``. In these
models they are the flash-attention forward and backward kernels (in the
trace: ``_fa_call`` and ``_fa_bwd_pallas``)."""
from chipbench import trace

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["traffic"].get("seq", 0) >= 512     # flash's auto-selection


def read(run):
    ms = 1e3 * run.trace.op_seconds_per_step(trace.is_mosaic)
    return ms or None
