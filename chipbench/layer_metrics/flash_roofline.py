"""The flash kernels' share of their roofline: the least time the chip
could take for the operations and bytes a step's attention requires (the
family's shape function against the published peaks), over the time the
kernels took. An earlier line says which peak bounds."""
from chipbench import trace

UNIT = "%"
LAYER = "kernels"
MOVES = "samples_per_s_chip"


def applies(cell):
    return cell["traffic"].get("seq", 0) >= 512


def read(run):
    took = run.trace.op_seconds_per_step(trace.is_mosaic)
    work = run.task.flash
    if not took or not work:
        return None
    per_chip = {k: v / run.cell["chips"] for k, v in work.items()}
    compute_s = per_chip["flops"] / run.peaks["flops_per_s"]
    memory_s = per_chip["bytes"] / run.peaks["hbm_bytes_per_s"]
    run.info(what="flash_roofline",
             bound="compute" if compute_s >= memory_s else "memory",
             compute_ms=1e3 * compute_s, memory_ms=1e3 * memory_s,
             flash_ms=1e3 * took)
    return 100 * max(compute_s, memory_s) / took
