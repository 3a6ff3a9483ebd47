"""Device time a step spends in the WINDOWED flash-attention backward
kernels (the sliding layers' calls), whichever of the backward's forms ran:
the leaf operations on device 0 that carry a name of
``horovod_tpu.ops.flash_attention.KERNEL_BWD_WINDOW``."""
from chipbench import kernels, window_kernels

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"
applies = window_kernels.applies


def read(run):
    return kernels.ms_a_step(run, window_kernels.names()[1])
