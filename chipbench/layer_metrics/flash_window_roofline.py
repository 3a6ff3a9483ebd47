"""The windowed flash kernels' share of their roofline: the least time the
chip could take for the operations and bytes the sliding layers' attention
REQUIRES (the family's ``flash_window`` work: visible pairs under the window
only, against the published peaks), over the time the windowed kernels took.
The masked halves of the band's tiles count as time and not as work. An
earlier line says which peak bounds."""
from chipbench import kernels, window_kernels

UNIT = "%"
LAYER = "kernels"
MOVES = "samples_per_s_chip"
applies = window_kernels.applies


def read(run):
    fwd, bwd = window_kernels.names()
    took_ms = kernels.ms_a_step(run, fwd + bwd)
    work = getattr(run.task, "flash_window", None)
    if not took_ms or not work:
        return None
    per_chip = {k: v / run.cell["chips"] for k, v in work.items()}
    compute_s = per_chip["flops"] / run.peaks["flops_per_s"]
    memory_s = per_chip["bytes"] / run.peaks["hbm_bytes_per_s"]
    run.info(what="flash_window_roofline",
             bound="compute" if compute_s >= memory_s else "memory",
             compute_ms=1e3 * compute_s, memory_ms=1e3 * memory_s,
             flash_window_ms=took_ms)
    return 100 * max(compute_s, memory_s) / (took_ms / 1e3)
