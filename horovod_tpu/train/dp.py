"""Data-parallel training harness — the minimum end-to-end slice.

Reference parity: the training loop every Horovod example script assembles
by hand (``examples/pytorch/pytorch_imagenet_resnet50.py``: init → broadcast
params → per-step backward → DistributedOptimizer allreduce → step). Here the
whole step is ONE compiled XLA program over the mesh: forward, backward,
fused gradient allreduce, and the optimizer update all inside ``jit`` +
``shard_map`` — data rides ICI, nothing bounces through the host.

This module is deliberately small: models plug in as flax Modules, optimizers
as optax transforms wrapped by ``horovod_tpu.optimizer.distributed``. The
step body here only describes the DP loss/update; program assembly, host
dispatch, scan folding and gradient accumulation are the shared
``step_builder`` machinery (docs/train_step.md).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from ..core import context_api as _ctx
from ..core import sentinel as _sentinel
from ..core.watchdog import monitored_step
from ..collectives import ops as _ops
from ..collectives.ops import effective_axis_size, force_axis_size1
from ..optimizer import broadcast_parameters
from .step_builder import (accumulate_gradients, build_program_set,
                           fold_scan, instrument_dispatch, make_dispatch)


class TrainState(NamedTuple):
    step: Any
    params: Any
    opt_state: Any
    # Model state that no gradient trains and the step carries along:
    # BatchNorm's running statistics, a router's selection bias and expert
    # counts (the flax collection "batch_stats"). {} for models with none.
    batch_stats: Any


def create_train_state(model, rng, sample_input,
                       optimizer: optax.GradientTransformation,
                       broadcast: bool = True, mesh=None) -> TrainState:
    """Init variables + optimizer state; broadcast from rank-0's process so
    all hosts agree (reference: ``hvd.broadcast_parameters`` at startup).

    The state is returned replicated over ``mesh`` (default: the context
    mesh) — pass the same mesh you give ``make_train_step``."""
    # One compiled program: eager flax init dispatches (and compiles) every
    # initializer and forward op on its own, which on an accelerator is
    # most of the wait before the first step.
    variables = jax.jit(lambda r, x: model.init(r, x, train=False))(
        rng, sample_input)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    if broadcast:
        params = broadcast_parameters(params)
        batch_stats = broadcast_parameters(batch_stats)
    opt_state = optimizer.init(params)
    state = TrainState(jnp.zeros((), jnp.int32), params, opt_state,
                       batch_stats)
    if mesh is None and _ctx.is_initialized():
        mesh = _ctx.mesh()
    if mesh is not None:
        # Committed to the mesh, like every state the step returns. An
        # uncommitted state is a different input TYPE to jit than the
        # mesh-committed one that comes back, so the second call traced,
        # lowered and compiled the whole step again (one v5e, cold:
        # +27 s ResNet-50, +52 s BERT-Large — PERF.md, PR 21).
        state = jax.device_put(state, NamedSharding(mesh, P()))
    return state


def make_train_step(model, optimizer: optax.GradientTransformation,
                    loss_fn: Callable[[Any, Any], Any], *,
                    axis_name: Optional[str] = None,
                    mesh=None,
                    donate: bool = True,
                    scan_steps: Optional[int] = None,
                    accum_steps: Optional[int] = None,
                    autotune: Optional[bool] = None,
                    sentinel=None):
    """Build the jitted DP train step: ``step(state, batch, labels) ->
    (state, loss)``. ``batch``/``labels`` are sharded over the rank axis,
    state is replicated; the gradient allreduce happens inside ``optimizer``
    (a ``horovod_tpu.optimizer.distributed`` transform).

    ``scan_steps=k`` wraps k consecutive steps in a device-side ``lax.scan``
    over the same batch (one dispatch, one sync): device throughput
    without host dispatch in the loop.
    Composes with ``sentinel``: the per-step health vectors stack to
    ``[k, n, 3]`` and the host ladder adjudicates every row.

    ``accum_steps=a`` microbatches the per-device batch a ways and
    accumulates gradients in a device-side scan before the SINGLE
    optimizer update — the gradient allreduce fires once per step, after
    accumulation (upstream's ``backward_passes_per_step``, but in-graph:
    no host round-trips between backwards). The per-device batch dim must
    be divisible by ``a``; BatchNorm stats thread through the microbatches
    sequentially.

    ``autotune``: when True — or by default when ``HOROVOD_AUTOTUNE=1`` is
    set (the reference's zero-user-code transparent tuning,
    parameter_manager.cc) — the returned step is a
    :class:`~horovod_tpu.tools.autotune.StepAutotuner` that tunes the
    gradient-fusion bucket size (``HOROVOD_FUSION_THRESHOLD``) against live
    throughput while training, logging trials to ``HOROVOD_AUTOTUNE_LOG``
    and locking in the best knobs after convergence. Same call contract;
    the chosen knobs are readable as ``step.chosen``.

    ``sentinel``: a :class:`~horovod_tpu.core.sentinel.Sentinel`, True, or
    (default) the ``HOROVOD_SENTINEL`` env/config switch. When engaged the
    step ALSO computes the fused in-graph health vector (one extra small
    all_gather, docs/numeric_integrity.md) and a where-guard that keeps
    params/opt_state untouched on a globally non-finite step, plus a
    second no-update probe program for consecutive bad steps (donated
    state aliases through, the update work is DCE'd — the two-program
    trick, built once in ``step_builder``). The call contract is
    unchanged; the policy object is readable as ``step.sentinel``."""
    sentinel = _sentinel.resolve(sentinel)
    if autotune is None:
        autotune = _ctx.is_initialized() and _ctx.context().config.autotune
    if autotune:
        return _autotuned_train_step(
            model, optimizer, loss_fn, axis_name=axis_name, mesh=mesh,
            donate=donate, scan_steps=scan_steps, accum_steps=accum_steps,
            sentinel=sentinel)
    mesh = mesh if mesh is not None else _ctx.mesh()
    if axis_name is not None:
        axis = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
            else axis_name
    elif _ctx.is_initialized() and mesh is _ctx.mesh():
        axis = _ctx.context().axis_name
    else:
        # A custom multi-axis mesh (e.g. create_hybrid_mesh for hierarchical
        # allreduce): the rank axis is the tuple of its axes — batch shards
        # over all of them, collectives reduce over all of them.
        axis = mesh.axis_names[0] if len(mesh.axis_names) == 1 \
            else tuple(mesh.axis_names)

    def make_sharded_step(opt, apply_update: bool):
        # Two bodies, one source of truth: the probe variant
        # (apply_update=False) never traces optimizer.update, so the
        # donated params/opt_state alias straight through and the dW
        # work whose only consumer was the update is DCE'd — the
        # step_builder two-program trick (a lax.cond would copy the
        # pass-through state instead).
        def sharded_step(state: TrainState, batch, labels):
            def run_grads(params, stats, b, y):
                variables = {"params": params}
                use_stats = len(jax.tree_util.tree_leaves(stats)) > 0
                with jax.named_scope("hvd_forward"):
                    if use_stats:
                        variables["batch_stats"] = stats
                        out, mutated = model.apply(variables, b, train=True,
                                                   mutable=["batch_stats"])
                        new_stats = mutated["batch_stats"]
                    else:
                        out = model.apply(variables, b, train=True)
                        new_stats = stats
                    return loss_fn(out, y), new_stats

            vg = jax.value_and_grad(run_grads, has_aux=True)
            if accum_steps is not None and accum_steps > 1:
                (loss, new_stats), grads = accumulate_gradients(
                    vg, state.params, state.batch_stats, (batch, labels),
                    accum_steps)
            else:
                (loss, new_stats), grads = vg(state.params,
                                              state.batch_stats, batch,
                                              labels)
            multi = effective_axis_size(axis) != 1  # known at trace time
            health = None
            if sentinel is not None:
                health = _sentinel.health_vector(
                    grads, state.params, axis=axis if multi else None)
            if multi:
                loss = jax.lax.pmean(loss, axis)
            if apply_update:
                updates, opt_state = opt.update(grads, state.opt_state,
                                                state.params)
                with jax.named_scope("hvd_optimizer"):
                    params = optax.apply_updates(state.params, updates)
                if multi:
                    # TrainState is declared replicated (out_specs P()); if
                    # the model's BatchNorm does not itself sync
                    # (axis_name=None), per-device stats would silently
                    # diverge — averaging makes them truly replicated (a
                    # no-op when the model already synced them). Routed
                    # through grouped_allreduce, NOT a per-leaf pmean
                    # tree_map: the stats ride the same fused/bucketed
                    # collective path as the gradients (one collective per
                    # bucket instead of one tiny all-reduce per BN moment —
                    # the exact pattern lint-monolithic-psum flags).
                    # Skipped on a 1-member axis: XLA does not reliably
                    # elide single-participant all-reduces.
                    new_stats = _ops.grouped_allreduce(
                        new_stats, _ops.Average, axis_name=axis)
                if sentinel is not None:
                    # In-graph skip guard: a globally non-finite step must
                    # not touch params/opt_state/stats on ANY rank. The
                    # global verdict comes from the already-gathered health
                    # vector (no second collective); jnp.where is an
                    # elementwise select, free of the lax.cond copy trap.
                    ok = health[:, 0].min() >= 1.0

                    def guard(new, old):
                        return jnp.where(ok, new, old)
                    params = jax.tree_util.tree_map(guard, params,
                                                    state.params)
                    opt_state = jax.tree_util.tree_map(guard, opt_state,
                                                       state.opt_state)
                    new_stats = jax.tree_util.tree_map(guard, new_stats,
                                                       state.batch_stats)
            else:
                params, opt_state, new_stats = (
                    state.params, state.opt_state, state.batch_stats)
            out_state = TrainState(state.step + 1, params, opt_state,
                                   new_stats)
            if sentinel is not None:
                return out_state, loss, health
            return out_state, loss

        if scan_steps is not None:
            sharded_step = fold_scan(sharded_step, scan_steps,
                                     sentinel is not None)

        if mesh.devices.size == 1:
            # 1-device world: no shard_map. The SPMD partitioner costs real
            # layout copies on TPU even with one participant (measured ~10%
            # on ResNet-50); under force_axis_size1 the collectives inside
            # (optimizer allreduce, pmean, BN stat sync) collapse to
            # identity, so the compiled program is bit-identical to plain
            # single-device training — the reference's 1-process behavior.
            inner_step = sharded_step

            def step(state, batch, labels):
                axes = axis if isinstance(axis, tuple) else (axis,)
                with force_axis_size1(*axes):
                    return inner_step(state, batch, labels)
        else:
            step = _shard_map(
                sharded_step, mesh=mesh,
                in_specs=(P(), P(axis), P(axis)),
                out_specs=(P(), P(), P()) if sentinel is not None
                else (P(), P()),
                check_vma=False)
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    programs = build_program_set(make_sharded_step, optimizer=optimizer,
                                 sentinel=sentinel)
    jitted = programs["apply"]
    dispatch = make_dispatch(programs, sentinel=sentinel,
                             scan_steps=scan_steps)

    marked = instrument_dispatch(dispatch, what="train_step",
                                 lower=jitted.lower)
    marked.lower = jitted.lower  # keep AOT introspection available
    if sentinel is not None:
        marked.lower_probe = programs["probe"].lower
        marked.sentinel = sentinel
    # Jit-step deadline monitor (core/watchdog.py, docs/failure_model.md):
    # unarmed this is a passthrough; armed, the blocking device fetch runs
    # on a watcher-visible thread so a step blocked inside an XLA
    # collective against a dead peer can be abandoned on deadline or
    # peer-death notification instead of hanging the process forever.
    return monitored_step(marked, what="train_step")


def _autotuned_train_step(model, optimizer, loss_fn, **build_kw):
    """HOROVOD_AUTOTUNE=1 engagement: wrap the step in a StepAutotuner
    that searches the GRAPH-SHAPE knobs live (the reference tunes fusion
    buffer + cycle time + hierarchical flags the same
    propose→measure→report way, parameter_manager.cc):

    - ``fusion_threshold_bytes`` — gradient bucket size;
    - ``hierarchical`` — staged reducescatter/allgather vs flat allreduce
      (only on a multi-axis rank mesh, where the choice exists).

    Both change ONLY the emitted HLO (identical numerics and step
    contract), so they are safe to search under a live training loop.
    ``scan_steps`` is deliberately NOT in this space: it changes how many
    optimizer updates one call performs — a caller-visible contract — so
    it remains an explicit ``StepAutotuner`` dimension for callers who
    own their loop (see tools/autotune.py's usage example)."""
    from ..core.logging import get_logger
    from ..collectives.ops import (fusion_threshold_override,
                                   hierarchical_override)
    from ..tools.autotune import Autotuner, CatDim, LogIntDim, StepAutotuner

    cfg = _ctx.context().config
    ctx_axis = _ctx.context().axis_name

    def build(fusion_threshold_bytes, hierarchical=None):
        inner = make_train_step(model, optimizer, loss_fn, autotune=False,
                                **build_kw)
        thr = int(fusion_threshold_bytes)

        def stepped(*args, **kwargs):
            # jit traces lazily (on first call), so the trial knobs are
            # scoped around every invocation — they reach THIS step's
            # trace and never leak into other functions traced while
            # tuning.
            with fusion_threshold_override(thr), \
                    hierarchical_override(hierarchical):
                return inner(*args, **kwargs)

        def lowered(*args, **kwargs):
            # AOT introspection must trace under the SAME knobs the step
            # executes with — lowering outside the overrides would show
            # the config-default program, not the tuned one.
            with fusion_threshold_override(thr), \
                    hierarchical_override(hierarchical):
                return inner.lower(*args, **kwargs)
        stepped.lower = lowered
        return stepped

    space = {"fusion_threshold_bytes": LogIntDim(1 << 20, 1 << 28)}
    if isinstance(ctx_axis, tuple) and len(ctx_axis) >= 2:
        space["hierarchical"] = CatDim((False, True))
    tuner = Autotuner(space, warmup_trials=cfg.autotune_warmup_samples,
                      max_trials=cfg.autotune_max_samples,
                      log_path=cfg.autotune_log)
    get_logger().info(
        "HOROVOD_AUTOTUNE: tuning fusion threshold live "
        "(%d warmup / %d max samples, %d steps each%s)",
        cfg.autotune_warmup_samples, cfg.autotune_max_samples,
        cfg.autotune_steps_per_sample,
        f", log={cfg.autotune_log}" if cfg.autotune_log else "")
    return StepAutotuner(build, space,
                         steps_per_trial=cfg.autotune_steps_per_sample,
                         tuner=tuner)
