"""Device time a step spends in the WINDOWED flash-attention forward kernel
(the sliding layers' calls): the leaf operations on device 0 that carry the
name ``horovod_tpu.ops.flash_attention.KERNEL_FWD_WINDOW``. ``flash_fwd_ms``
reads the unwindowed calls (the full layers') and none of these."""
from chipbench import kernels, window_kernels

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"
applies = window_kernels.applies


def read(run):
    return kernels.ms_a_step(run, window_kernels.names()[0])
