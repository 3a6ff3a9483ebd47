"""The windowed flash-attention kernels' instruction names, found as
``kernels.py`` finds the unwindowed ones."""

import importlib


def names():
    """``(forward's names, backward's names)`` of the windowed flash
    kernels, or None where the library under test has none (the parent of
    the PR that added the window: the readers then apply to no cell)."""
    flash = importlib.import_module("horovod_tpu.ops.flash_attention")
    if not hasattr(flash, "KERNEL_FWD_WINDOW"):
        return None
    return (flash.KERNEL_FWD_WINDOW,), tuple(flash.KERNEL_BWD_WINDOW)


def applies(cell):
    """A cell whose configuration has a window, run at a length at which
    flash attention is chosen, by a library that names the kernels."""
    return bool(cell["config"].get("sliding_window")) \
        and cell["traffic"].get("seq", 0) >= 512 and names() is not None
