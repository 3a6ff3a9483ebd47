"""DistributedOptimizer — gradient-averaging optimizer wrapper.

Reference parity: ``horovod/torch/optimizer.py`` + ``horovod/tensorflow/
__init__.py DistributedOptimizer/DistributedGradientTape`` (SURVEY.md §2.4,
§3.2). The reference hooks each parameter's grad-ready event, enqueues an
async allreduce per tensor, and blocks in ``optimizer.step()`` until all
handles complete — negotiation, fusion buffer, cycle-time batching.

TPU-native: the optimizer is an ``optax``-style gradient transformation whose
``update`` performs ONE fused in-graph allreduce of the whole gradient pytree
(``grouped_allreduce`` — the compile-time fusion buffer) and then applies the
inner optimizer. Because it runs inside the user's jitted train step, XLA
overlaps the collective with the backward pass where dataflow allows —
the async-handle machinery of the reference exists for free.

``backward_passes_per_step`` (local gradient aggregation, reference:
``gradient_aggregation*.py``) accumulates k micro-batch gradients locally and
communicates once every k steps.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ..collectives import ops as _ops
from ..collectives.compression import Compression, Compressor
from ..core.process_sets import ProcessSet


class DistributedState(NamedTuple):
    inner_state: Any
    acc: Any          # local gradient accumulator (zeros when bpps == 1)
    counter: Any      # int32 micro-step counter


def distributed(inner: optax.GradientTransformation, *,
                op: str = _ops.Average,
                axis_name: Optional[str] = None,
                process_set: Optional[ProcessSet] = None,
                compression: Compressor = Compression.none,
                backward_passes_per_step: int = 1,
                prescale_factor: float = 1.0,
                postscale_factor: float = 1.0,
                average_aggregated_gradients: bool = True,
                ) -> optax.GradientTransformation:
    """Wrap ``inner`` so updates see globally-reduced gradients.

    Use inside a jitted/shard_mapped train step over the rank axis. With
    ``backward_passes_per_step=k`` the collective fires every k-th call;
    intermediate calls return zero updates (apply them unconditionally —
    params are unchanged on non-boundary steps, matching the reference's
    semantics where ``step()`` is only effective at the boundary).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    k = backward_passes_per_step

    def reduce_grads(grads):
        return _ops.grouped_allreduce(
            grads, op, process_set=process_set, axis_name=axis_name,
            compression=compression, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)

    if k == 1:
        def init(params):
            return DistributedState(inner.init(params), (),
                                    jnp.zeros((), jnp.int32))

        def update(grads, state, params=None, **extra):
            g = reduce_grads(grads)
            with jax.named_scope("hvd_optimizer"):
                updates, inner_state = inner.update(
                    g, state.inner_state, params, **extra)
            return updates, DistributedState(inner_state, (),
                                             state.counter + 1)

        return optax.GradientTransformation(init, update)

    def init(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return DistributedState(inner.init(params), zeros,
                                jnp.zeros((), jnp.int32))

    def update(grads, state, params=None, **extra):
        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, grads)
        count = state.counter + 1
        boundary = (count % k) == 0

        def on_boundary(operand):
            acc_, inner_state = operand
            scale = 1.0 / k if average_aggregated_gradients else 1.0
            g = jax.tree_util.tree_map(lambda a: a * scale, acc_)
            g = reduce_grads(g)
            with jax.named_scope("hvd_optimizer"):
                updates, new_inner = inner.update(g, inner_state, params,
                                                  **extra)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, acc_)
            return updates, new_inner, zeros

        def off_boundary(operand):
            acc_, inner_state = operand
            zero_updates = jax.tree_util.tree_map(jnp.zeros_like, acc_)
            return zero_updates, inner_state, acc_

        updates, inner_state, acc = jax.lax.cond(
            boundary, on_boundary, off_boundary, (acc, state.inner_state))
        return updates, DistributedState(inner_state, acc, count)

    return optax.GradientTransformation(init, update)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters=None,
                         compression: Compressor = Compression.none,
                         backward_passes_per_step: int = 1,
                         op: str = _ops.Average,
                         gradient_predivide_factor: float = 1.0,
                         process_set: Optional[ProcessSet] = None,
                         axis_name: Optional[str] = None,
                         ) -> optax.GradientTransformation:
    """API-parity constructor matching ``hvd.DistributedOptimizer(...)``
    (reference: torch/optimizer.py). ``named_parameters`` is accepted for
    signature compatibility and ignored (JAX pytrees carry structure).

    ``gradient_predivide_factor`` splits the averaging between pre- and
    post-scale exactly as the reference does: prescale = 1/(factor·size) is
    expressed here as op=Sum with pre/post factors when factor != 1.
    """
    if gradient_predivide_factor == 1.0:
        return distributed(optimizer, op=op, axis_name=axis_name,
                           process_set=process_set, compression=compression,
                           backward_passes_per_step=backward_passes_per_step)

    # Reference formula (torch/optimizer.py): gradients are pre-divided by
    # (factor · size) before the SUM allreduce and post-multiplied by factor
    # after, netting an average computed in two stages for numeric headroom.
    # The 1/size part needs the axis size, only known at trace time, so it is
    # applied to the incoming grads here; the collective runs op=Sum with
    # the static postscale.
    base = distributed(optimizer, op=_ops.Sum, axis_name=axis_name,
                       process_set=process_set, compression=compression,
                       backward_passes_per_step=backward_passes_per_step,
                       postscale_factor=gradient_predivide_factor)

    def init(params):
        return base.init(params)

    def update(grads, state, params=None, **extra):
        axis = _ops._axis(axis_name)
        if process_set is not None and process_set.process_set_id != 0:
            n = process_set.size()
        else:
            n = jax.lax.axis_size(axis)
        pre_f = 1.0 / (gradient_predivide_factor * n)
        grads = jax.tree_util.tree_map(lambda g: g * pre_f, grads)
        return base.update(grads, state, params, **extra)

    return optax.GradientTransformation(init, update)
