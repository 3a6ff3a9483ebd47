"""Device time a step spends in the two flash-attention backward kernels (dQ,
and dK/dV): the leaf operations on device 0 that carry their instruction
names (``horovod_tpu.ops.flash_attention.KERNEL_BWD``)."""
from chipbench import kernels

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["traffic"].get("seq", 0) >= 512 \
        and kernels.flash_names() is not None


def read(run):
    return kernels.ms_a_step(run, kernels.flash_names()[1])
