"""Shared flash-attention auto-resolution for the model families."""

import os

import jax

# Auto crossover, measured on v5e BERT-Large (seq 512): with the dedicated
# blockwise backward kernels and 512-token blocks the Pallas kernel reaches
# ~54k tok/s/chip vs ~47k for XLA's materialised attention, and the gap
# grows with sequence length (~5x fwd+bwd at T=4096, D=64). Below ~512
# tokens the grid is too small to amortise kernel overhead. Ring attention
# calls the kernel explicitly with residuals, bypassing this heuristic.
#
# The 512 boundary is grid-size sensitive, not universal (r4): Mixtral's
# 8-head seq-512 config measured materialised 6.5% FASTER (half BERT's
# heads = half the grid), while seq 1024 favors flash by 21% even at 8
# heads. Models near the boundary with few heads should pass an explicit
# use_flash.
AUTO_MIN_SEQ = 512


def _gspmd_safe() -> bool:
    """A ``pallas_call`` is safe when one of: the kernels' own
    ``custom_partitioning`` wrappers are active (batch*head sharded,
    sequence/depth replicated — the default on TPU), we are tracing
    per-device code (the context rank axis is bound, i.e. inside the DP
    ``shard_map``), or there is only one device."""
    from ..collectives.ops import static_axis_size
    from ..core import context_api as _ctx
    from ..ops.flash_attention import _partition_enabled
    if _partition_enabled():
        return True
    if _ctx.is_initialized() \
            and static_axis_size(_ctx.context().axis_name) is not None:
        return True
    return len(jax.devices()) == 1


def resolve_flash(use_flash, seq_len=None):
    """None = auto: the Pallas kernel on TPU for sequences >= AUTO_MIN_SEQ
    (short sequences are faster through XLA and interpret-mode Pallas is
    orders of magnitude slower on CPU meshes). GSPMD composition is handled
    by the kernels' custom_partitioning wrappers. ``HOROVOD_FLASH_ATTENTION
    =0/1`` overrides the auto choice (config-system parity: explicit config
    beats env beats default)."""
    if use_flash is not None:
        return bool(use_flash)
    env = os.environ.get("HOROVOD_FLASH_ATTENTION")
    if env is not None:
        return env not in ("0", "false", "False", "")
    if jax.default_backend() != "tpu":
        return False
    if seq_len is not None and seq_len < AUTO_MIN_SEQ:
        return False
    return _gspmd_safe()
