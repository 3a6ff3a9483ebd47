"""The chunked delta rule's share of its roofline: the least time the chip
could take for the operations and bytes a step's delta rule requires
(``chipbench/delta_work.py`` at its fixed chunk, against the published
peaks) over the device time under ``hvd_kda_scan``. An earlier line says
which peak bounds."""
from chipbench import kda_scopes

UNIT = "%"
LAYER = "kernels"
MOVES = "samples_per_s_chip"
applies = kda_scopes.applies


def read(run):
    took = kda_scopes.of(run)["hvd_kda_scan"] / 1e3
    work = run.task.kda_scan
    if not took:
        return None
    per_chip = {k: v / run.cell["chips"] for k, v in work.items()}
    compute_s = per_chip["flops"] / run.peaks["flops_per_s"]
    memory_s = per_chip["bytes"] / run.peaks["hbm_bytes_per_s"]
    run.info(what="kda_scan_roofline",
             bound="compute" if compute_s >= memory_s else "memory",
             compute_ms=1e3 * compute_s, memory_ms=1e3 * memory_s,
             kda_scan_ms=1e3 * took)
    return 100 * max(compute_s, memory_s) / took
