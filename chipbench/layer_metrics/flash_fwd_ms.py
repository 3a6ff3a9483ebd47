"""Device time a step spends in the flash-attention forward kernel: the leaf
operations on device 0 that carry the kernel's own instruction name
(``horovod_tpu.ops.flash_attention.KERNEL_FWD``). With ``flash_bwd_ms`` it
sums to ``flash_ms`` while flash attention is the step's only Mosaic kernel."""
from chipbench import kernels

UNIT = "ms"
LAYER = "kernels"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["traffic"].get("seq", 0) >= 512 \
        and kernels.flash_names() is not None


def read(run):
    return kernels.ms_a_step(run, kernels.flash_names()[0])
