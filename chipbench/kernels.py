"""Device time of single Pallas kernels, found by the instruction names the
library gives them (``pallas_call(name=...)``: XLA names the custom call, and
so the ``XLA Ops`` event, after it), not by what Python function happens to
enclose the call."""

import importlib

from chipbench import trace


def flash_names():
    """``(forward's names, backward's names)`` of the flash-attention
    kernels, or None where the library under test does not name them (the
    parent of the PR that did: the per-kernel readers then apply to no cell)."""
    flash = importlib.import_module("horovod_tpu.ops.flash_attention")
    if not hasattr(flash, "KERNEL_FWD"):
        return None
    return (flash.KERNEL_FWD,), tuple(flash.KERNEL_BWD)


def ms_a_step(run, names):
    """Device milliseconds a step in leaf operations named ``names``; None
    when the trace holds none (the harness then refuses the line)."""
    return 1e3 * run.trace.op_seconds_per_step(
        lambda event: trace.op_name(event) in names) or None
