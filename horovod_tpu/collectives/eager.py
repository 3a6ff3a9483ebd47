"""Eager per-rank collectives over the global mesh.

The reference's op surface is *eager*: every process calls
``hvd.allreduce(tensor)`` on its own tensor (SURVEY.md §3.2). JAX is
single-controller, so the per-rank view is an array with a leading
``size()``-length rank axis (or an array already sharded over the mesh).
These wrappers shard the input over the mesh's rank axis, run the in-graph
op from ``collectives/ops.py`` under ``shard_map``, and return the result —
real XLA collectives on the real devices, usable from plain Python for
parity tests, parameter broadcast at startup, and host-driven tools.

Hot-path users should call the in-graph ops inside their own jitted step
instead; these wrappers pay one dispatch per call (but no negotiation, no
fusion-buffer memcpy — the things the reference pays per call).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from horovod_tpu.core import context_api as _ctx
from ..core.process_sets import ProcessSet
from .compression import Compression, Compressor
from . import ops as _ops
from ..tools import mismatch as _mismatch


def _mesh():
    return _ctx.mesh()


# Cache of jitted shard_map wrappers keyed by the parameters that shape the
# trace. Without this every eager call would rebuild closure+jit and pay a
# full retrace (~20 ms); with it, repeated calls (e.g. broadcast_parameters
# over hundreds of leaves) hit jax's own trace cache via a stable callable.
_jit_cache: dict = {}


def _run(builder, cache_key, tensor, out_replicated: bool):
    ctx = _ctx.context()
    ax = ctx.axis_name
    if _mismatch.MismatchDetector.enabled():
        # Debug-mode cross-process divergence check (HOROVOD_MISMATCH_CHECK;
        # SURVEY.md §5.2): record this collective's signature for verify().
        # Only PRIMITIVE key parts go into the signature — str() of rich
        # objects embeds memory addresses that differ per process and would
        # make every verify() a false mismatch.
        op = "|".join(str(k) for k in cache_key[1:]
                      if isinstance(k, (int, float, str, bool, bytes,
                                        tuple)))
        _mismatch.maybe_record(str(cache_key[0]), tensor, op=op)
    key = (ctx.mesh, ax, out_replicated) + cache_key
    jitted = _jit_cache.get(key)
    if jitted is None:
        out_spec = P() if out_replicated else P(ax)
        # check_vma=False: some collectives (all_gather-based Product,
        # ppermute butterflies) produce values that ARE replicated but whose
        # replication XLA's varying-axes inference cannot prove.
        shmapped = _shard_map(builder(), mesh=ctx.mesh,
                              in_specs=P(ax), out_specs=out_spec,
                              check_vma=False)
        jitted = jax.jit(shmapped)
        _jit_cache[key] = jitted
    tl = ctx.timeline
    if tl is not None:
        # Host-side lifecycle recording (reference: timeline.cc phases).
        # Under XLA the on-device phases live in the jax.profiler trace
        # (tools/profiler.py); this records the host dispatch span.
        name = str(cache_key[0]).upper()
        tl.activity_start(name, "DISPATCH")
        out = jitted(tensor)
        tl.activity_end(name, "DISPATCH")
        tl.mark_cycle()
        return out
    return jitted(tensor)


def _ps_key(process_set):
    # Key on the member ranks, not the id: ids restart after shutdown/init,
    # so two different sets could share an id across context lifetimes.
    return None if process_set is None else process_set.ranks


def _check_stacked(tensor, n, exact=True):
    for leaf in jax.tree_util.tree_leaves(tensor):
        if exact and leaf.shape[0] != n:
            raise ValueError(
                f"eager collectives expect a leading rank axis of exactly "
                f"world size {n}; got shape {leaf.shape}")
        if not exact and leaf.shape[0] % n != 0:
            raise ValueError(
                f"eager allgather expects a leading axis divisible by "
                f"world size {n}; got shape {leaf.shape}")


def allreduce(tensor: Any, op: str = _ops.Average, *,
              process_set: Optional[ProcessSet] = None,
              compression: Compressor = Compression.none,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0) -> Any:
    """Per-rank allreduce. ``tensor`` leaves are stacked ``[size, ...]``;
    returns the reduced value (identical across ranks, returned once) for
    the global set, or the per-rank stacked result when a process set is
    given (non-members keep their input)."""
    n = _ctx.size()
    _check_stacked(tensor, n)
    replicated = process_set is None or process_set.process_set_id == 0

    def builder():
        def body(x):
            x = jax.tree_util.tree_map(lambda l: l[0], x)
            y = _ops.allreduce(x, op, process_set=process_set,
                               compression=compression,
                               prescale_factor=prescale_factor,
                               postscale_factor=postscale_factor)
            if not replicated:
                y = jax.tree_util.tree_map(lambda l: l[None], y)
            return y
        return body

    key = ("allreduce", op, _ps_key(process_set), compression,
           prescale_factor, postscale_factor)
    return _run(builder, key, tensor, out_replicated=replicated)


def grouped_allreduce(tensors: Any, op: str = _ops.Average, **kw) -> Any:
    return allreduce(tensors, op, **kw)


def allgather(tensor: Any, *, process_set: Optional[ProcessSet] = None) -> Any:
    """Per-rank allgather: input leaves ``[size * k, ...]`` (k rows per
    rank). Global set: returns the rank-order concatenation (replicated).
    Process set: each rank gathers within its group, so the result is
    stacked per-rank ``[size, group_k, ...]``. Only MEMBER rows are
    specified for a proper subset — ragged sets on the padded-group path
    leave non-member rows with other groups' data (reference semantics:
    non-participants never call the op; see ``ops.allgather``)."""
    n = _ctx.size()
    _check_stacked(tensor, n, exact=False)
    replicated = process_set is None or process_set.process_set_id == 0

    def builder():
        def body(x):
            y = _ops.allgather(x, process_set=process_set)
            if not replicated:
                y = jax.tree_util.tree_map(lambda l: l[None], y)
            return y
        return body

    key = ("allgather", _ps_key(process_set))
    return _run(builder, key, tensor, out_replicated=replicated)


def broadcast(tensor: Any, root_rank: int = 0, *,
              process_set: Optional[ProcessSet] = None) -> Any:
    """Per-rank broadcast of stacked ``[size, ...]`` input; returns root's
    row (replicated) for the global set, stacked rows for a subset."""
    n = _ctx.size()
    _check_stacked(tensor, n)
    replicated = process_set is None or process_set.process_set_id == 0

    def builder():
        def body(x):
            x = jax.tree_util.tree_map(lambda l: l[0], x)
            y = _ops.broadcast(x, root_rank, process_set=process_set)
            if not replicated:
                y = jax.tree_util.tree_map(lambda l: l[None], y)
            return y
        return body

    key = ("broadcast", root_rank, _ps_key(process_set))
    return _run(builder, key, tensor, out_replicated=replicated)


def broadcast_(arrays: Any, root_rank: int = 0) -> Any:
    """Broadcast already-replicated host values from ``root_rank``'s process
    to every process (multi-host). Single-host: identity. This is the
    parameter-broadcast primitive used by ``broadcast_parameters``."""
    if jax.process_count() == 1:
        return arrays
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(
        arrays, is_source=jax.process_index() == root_rank)


def alltoall(tensor: Any, *, process_set: Optional[ProcessSet] = None) -> Any:
    """Per-rank alltoall on stacked input ``[size, m, ...]`` (each rank's
    local tensor is ``[m, ...]``, with m divisible by size); output stacked
    ``[size, m, ...]`` of received chunks."""
    n = _ctx.size()
    _check_stacked(tensor, n)

    def builder():
        def body(x):
            x = jax.tree_util.tree_map(lambda l: l[0], x)
            y = _ops.alltoall(x, process_set=process_set)
            return jax.tree_util.tree_map(lambda l: l[None], y)
        return body

    key = ("alltoall", _ps_key(process_set))
    return _run(builder, key, tensor, out_replicated=False)


def reducescatter(tensor: Any, op: str = _ops.Sum, *,
                  process_set: Optional[ProcessSet] = None) -> Any:
    """Per-rank reducescatter on stacked ``[size, m, ...]``; output stacked
    ``[size, m/size, ...]`` (rank i's chunk in row i)."""
    n = _ctx.size()
    _check_stacked(tensor, n)

    def builder():
        def body(x):
            x = jax.tree_util.tree_map(lambda l: l[0], x)
            y = _ops.reducescatter(x, op, process_set=process_set)
            return jax.tree_util.tree_map(lambda l: l[None], y)
        return body

    key = ("reducescatter", op, _ps_key(process_set))
    return _run(builder, key, tensor, out_replicated=False)


def adasum_allreduce(tensor: Any, **kw) -> Any:
    """Eager Adasum over stacked per-rank gradients; returns the combined
    gradient (replicated)."""
    n = _ctx.size()
    _check_stacked(tensor, n)

    def builder():
        def body(x):
            x = jax.tree_util.tree_map(lambda l: l[0], x)
            from .adasum import adasum_allreduce as _ad
            return _ad(x, **kw)
        return body

    def stable(k, v):
        # ProcessSet (and anything else rich) must key on stable content:
        # str() embeds a memory address, which both defeats the jit cache
        # (permanent retrace) and differs per process (false mismatch).
        if isinstance(v, ProcessSet):
            return _ps_key(v)
        return v if isinstance(v, (int, float, str, type, bool,
                                   type(None))) else str(v)

    key = ("adasum",) + tuple(sorted(
        (k, stable(k, v)) for k, v in kw.items()))
    return _run(builder, key, tensor, out_replicated=True)
