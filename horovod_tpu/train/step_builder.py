"""Composable step-program builder: ONE implementation of the tricks the
hand-built step variants used to re-implement separately.

Reference parity: upstream Horovod exposes exactly one of these knobs —
``backward_passes_per_step`` on ``DistributedOptimizer``
(``horovod/torch/optimizer.py``), host-side gradient accumulation with the
allreduce fired on the k-th backward. Here the same features are *graph*
features composed at trace time, plus the ones the reference cannot
express (see docs/train_step.md for the full feature lattice):

- **two-program donation/DCE trick** (:func:`build_program_set`): a probe
  or skip program that never traces ``optimizer.update`` lets donated
  params/opt_state alias straight through (zero optimizer HBM) AND lets
  XLA dead-code-eliminate the dW work whose only consumer was the skipped
  update. A ``lax.cond`` inside ONE program cannot do either — its
  pass-through copies measured the entire saving away (CLAUDE.md, the
  ``lax.cond`` trap: +22% on Mixtral from the two-program form).
- **host dispatch** (:func:`make_dispatch`): the single host-side
  dispatcher over that program set — sentinel containment picks the probe,
  cadence deferral picks the skip program off-phase, everything else runs
  apply — with the step counter phase-seeded from ``state.step`` so
  checkpoint/elastic resume keeps the cadence phase.
- **scan folding** (:func:`fold_scan`): k device-side steps per dispatch,
  stacking the per-step health vectors ``[k, n, 3]`` so the sentinel
  ladder still sees every step (scan × sentinel used to be a
  ``ValueError`` for no structural reason).
- **gradient accumulation** (:func:`accumulate_gradients`): microbatch
  the local shard, accumulate grads in a ``lax.scan`` carry, reduce ONCE
  after the loop — the wire-bytes discipline ``lint-accum-psum-order``
  enforces repo-wide.
- **pipeline-parallel step** (:func:`make_pipeline_train_step`): the
  ``parallel/pipeline.py`` microbatch schedules (GPipe AD / interleaved
  1F1B) wrapped in the same program-set machinery.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

from ..core import context_api as _ctx
from ..core import sentinel as _sentinel
from ..core import telemetry as _telemetry
from ..core.watchdog import monitored_step


# ------------------------------------------------------------ program set

def build_program_set(make_program: Callable[[Any, bool], Any], *,
                      optimizer=None, pair=None,
                      sentinel=None) -> Dict[str, Any]:
    """The minimal jitted-program set for one feature combination.

    ``make_program(opt, apply_update)`` is the kind-specific factory (DP
    shard_map body, GSPMD annotated body, pipeline body) returning a
    jitted step; this function decides *which* programs exist:

    ========================  ==========================================
    features                  programs
    ========================  ==========================================
    (none)                    ``apply``
    cadence (``pair``)        ``apply`` (pair.apply), ``skip`` (pair.skip)
    sentinel                  ``apply``, ``probe``
    cadence + sentinel        ``apply``, ``skip``, and ONE shared
                              ``probe`` — the probe never traces any
                              ``optimizer.update``, so it is identical
                              whichever optimizer it nominally pairs with
    ========================  ==========================================

    The probe/skip programs are where the donation/DCE trick lives: built
    with ``apply_update=False`` (probe) or the pair's frozen-bank skip
    optimizer, the untouched donated state aliases through and XLA DCEs
    the dead dW work. Implemented here ONCE; the step factories only
    describe their loss/update body.
    """
    opt_apply = pair.apply if pair is not None else optimizer
    programs: Dict[str, Any] = {"apply": make_program(opt_apply, True),
                                "skip": None, "probe": None}
    if pair is not None:
        programs["skip"] = make_program(pair.skip, True)
    if sentinel is not None:
        programs["probe"] = make_program(opt_apply, False)
    return programs


# --------------------------------------------------------- host dispatch

def make_dispatch(programs: Dict[str, Any], *, sentinel=None,
                  every: int = 1, scan_steps: Optional[int] = None):
    """The single host-side dispatcher over a program set.

    Per call, in precedence order: the sentinel's containment state picks
    the ``probe`` program (no update anywhere — the suspect state is
    held); an off-phase cadence counter picks the ``skip`` program (the
    deferred pair's frozen-bank optimizer still updates the dense
    params); otherwise ``apply`` runs. With neither feature engaged the
    apply program is returned directly — zero dispatch overhead.

    The step counter is seeded from ``state.step`` on the first call (not
    0) so a checkpoint / elastic resume keeps the apply-vs-skip cadence
    PHASE: a job that restarts mid-window must not stretch the window, or
    the apply program's update scale (k baked in by ``deferred_pair``)
    and the real number of accumulated skip steps disagree. It advances
    by ``scan_steps`` per dispatch (a folded dispatch IS k steps), and
    the sentinel ladder is fed every stacked health row — stopping at the
    first rollback/evict verdict — so scan no longer hides bad steps from
    the policy engine.

    Preserves the public ``(state, loss)`` contract: the health vector the
    jitted programs append is decoded and stripped here.
    """
    every = int(every or 1)
    k = int(scan_steps or 1)
    if sentinel is None and every == 1:
        return programs["apply"]
    step_apply = programs["apply"]
    step_skip = programs["skip"] if programs.get("skip") is not None \
        else programs["apply"]
    step_probe = programs.get("probe")
    counter = {"n": None}

    def dispatch(state, *rest):
        if counter["n"] is None:
            try:
                counter["n"] = int(state.step)
            except jax.errors.ConcretizationTypeError:
                # Abstract tracing (hvd-analyze / make_jaxpr): no policy
                # decisions are made on tracers — fall back to 0.
                counter["n"] = 0
        base = counter["n"]
        counter["n"] += k
        if sentinel is not None and sentinel.in_containment:
            fn = step_probe
        elif counter["n"] % every != 0:
            fn = step_skip
        else:
            fn = step_apply
        out = fn(state, *rest)
        if sentinel is None:
            return out
        new_state, loss, health = out
        if isinstance(health, jax.core.Tracer):
            # Abstract trace: the health vector has no concrete value and
            # the ladder must not run.
            return new_state, loss
        raw = np.asarray(health, np.float32)
        rows = raw if raw.ndim == 3 else raw[None]  # [k, n, 3]
        for i, row in enumerate(rows):
            action = sentinel.observe(_sentinel.decode_health(row),
                                      base + i + 1)
            if action.kind == "rollback":
                new_state = sentinel.do_rollback(new_state)
                break
            if action.kind in ("evict", "abort"):
                sentinel.do_evict(action)
                break
        return new_state, loss

    return dispatch


def _abstract(x):
    """One argument leaf as the shape, dtype and sharding jit keyed its
    compilation on. An uncommitted array is wherever jit puts it: its
    sharding is left out, as jit leaves it out of the key."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=aval.weak_type)


def instrument_dispatch(dispatch: Callable, *, what: str,
                        lower: Optional[Callable] = None) -> Callable:
    """The host-side boundary of every step kind (DP, pipeline, GSPMD):
    one ``step.dispatch`` span round the call of ``dispatch`` with the
    call's ordinal as ``seq``, ``hvd_dispatches_total``, and the
    reference's MARK_CYCLES timeline record (``<WHAT>/DISPATCH`` activity
    + cycle marker; device phases live in the jax.profiler xplane).

    The first call, with telemetry on, also keeps the abstract signature of
    its arguments beside ``lower`` (the apply program's AOT handle): what
    ``telemetry.program(what)`` needs to read the optimized program back
    without compiling it again. No later call keeps or walks anything.

    The timeline is read PER CALL so start_timeline/stop_timeline work in
    any order relative to building the step, and a closed timeline is
    never written to. Nothing here reads the device: the dispatch is async
    and the loss is still a future — step timing belongs to the watchdog
    span, which sees values the host already fetched."""
    calls = [0]
    tensor = what.upper()
    counted = _telemetry.series_id("hvd_dispatches_total", what=what)

    def run(*args, **kwargs):
        seq = calls[0]
        calls[0] = seq + 1
        if seq == 0 and lower is not None and _telemetry.enabled():
            _telemetry.register_program(what, lower, jax.tree_util.tree_map(
                _abstract, (args, kwargs)))
        _telemetry.inc_series("hvd_dispatches_total", counted)
        tl = _ctx.context().timeline if _ctx.is_initialized() else None
        if tl is not None and getattr(tl, "_closed", False):
            tl = None
        if tl is not None:
            tl.activity_start(tensor, "DISPATCH")
        # The clock is read here and not by a ``span()`` object: on the
        # loop's thread every Python frame is run cache-cold and costs ten
        # times its timeit figure (PERF.md section 6, PR 25).
        timed = _telemetry.enabled()
        t0 = time.perf_counter_ns() if timed else 0
        with TraceAnnotation("hvd:step.dispatch"):
            out = dispatch(*args, **kwargs)
        if timed:
            _telemetry.record_span("step.dispatch", seq, t0,
                                   time.perf_counter_ns())
        if tl is not None:
            tl.activity_end(tensor, "DISPATCH")
            tl.mark_cycle()
        return out

    return run


# ---------------------------------------------------------- scan folding

def fold_scan(inner: Callable, scan_steps: int, with_health: bool):
    """Fold k consecutive steps into one dispatch via ``lax.scan`` over
    the same batch (one dispatch, one sync: device throughput without
    host dispatch in the loop).

    With a sentinel engaged the per-step health vectors stack to
    ``[k, n, 3]`` so the host ladder still adjudicates every inner step;
    the in-graph where-guard inside ``inner`` keeps a non-finite inner
    step from touching state regardless of what the host later decides.
    """
    k = int(scan_steps)
    if with_health:
        def stepped(state, *data):
            def body(st, _):
                st, loss, health = inner(st, *data)
                return st, (loss, health)
            state, (losses, healths) = jax.lax.scan(body, state, None,
                                                    length=k)
            return state, losses[-1], healths
        return stepped

    def stepped(state, *data):
        def body(st, _):
            st, loss = inner(st, *data)
            return st, loss
        state, losses = jax.lax.scan(body, state, None, length=k)
        return state, losses[-1]
    return stepped


# -------------------------------------------------- gradient accumulation

def accumulate_gradients(vg: Callable, params, aux0, data,
                         accum_steps: int):
    """Microbatch gradient accumulation inside one jitted step.

    Splits every array in ``data`` (shared leading batch dim, which under
    ``shard_map`` is the LOCAL shard) into ``accum_steps`` microbatches,
    runs ``vg(params, aux, *microbatch) -> ((loss, new_aux), grads)`` over
    them in a ``lax.scan`` — grads and loss accumulate in the carry, the
    aux (BatchNorm stats) threads through sequentially — and returns
    ``((mean_loss, final_aux), mean_grads)``.

    The reduction discipline is the point (``lint-accum-psum-order``):
    nothing cross-device happens inside the loop. Grads accumulate
    locally; the caller's single post-loop ``optimizer.update`` carries
    the one allreduce (explicit in ``optimizer.distributed`` for DP,
    implicit from the sharding under GSPMD). A psum per microbatch would
    move ``accum_steps``× the wire bytes for the same result — upstream's
    ``backward_passes_per_step`` (horovod/torch/optimizer.py) exists for
    exactly this reason. The sentinel health vector is likewise computed
    by the caller on the accumulated grads: one all_gather per step, not
    per microbatch.
    """
    a = int(accum_steps)
    if a < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    for x in data:
        if x.shape[0] % a:
            raise ValueError(
                f"leading batch dim {x.shape[0]} is not divisible by "
                f"accum_steps={a} (shapes are per-device under shard_map)")
    micro = tuple(x.reshape((a, x.shape[0] // a) + x.shape[1:])
                  for x in data)
    grads0 = jax.tree_util.tree_map(jnp.zeros_like, params)

    def body(carry, mb):
        grads_acc, loss_acc, aux = carry
        (loss, aux), grads = vg(params, aux, *mb)
        grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        return (grads_acc, loss_acc + loss.astype(loss_acc.dtype),
                aux), None

    (grads_acc, loss_acc, aux), _ = jax.lax.scan(
        body, (grads0, jnp.zeros((), jnp.float32), aux0), micro)
    inv = 1.0 / a
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads_acc)
    return (loss_acc * inv, aux), grads


# ------------------------------------------------- pipeline-parallel step

class PipelineTrainState(NamedTuple):
    step: Any
    stage_params: Any  # stacked [n_stages, ...] leaves; stage i on rank i
    opt_state: Any     # optimizer state vmapped over the stage dim


def create_pipeline_train_state(stage_params,
                                optimizer) -> PipelineTrainState:
    """Init the pipeline state from STACKED stage params (leading
    ``[n_stages, ...]`` dim on every leaf — the tests/test_parallel.py
    idiom). The optimizer state is ``vmap(optimizer.init)`` over that dim
    so each stage's moments shard to the rank that owns its parameters —
    nothing about a stage lives off its device."""
    opt_state = jax.vmap(optimizer.init)(stage_params)
    return PipelineTrainState(jnp.zeros((), jnp.int32), stage_params,
                              opt_state)


def make_pipeline_train_step(stage_fn: Callable, loss_fn: Callable,
                             optimizer, *, mesh, axis_name: str = "pp",
                             dp_axis_name: Optional[str] = None,
                             schedule: str = "interleaved",
                             donate: bool = True, pair=None):
    """Pipeline-parallel train step over ``parallel/pipeline.py``:
    ``step(state, x_microbatches, targets) -> (state, loss)``.

    ``schedule="interleaved"`` (alias ``"1f1b"``) uses the hand-scheduled
    1F1B interleave — O(n) activation memory, recompute-in-backward;
    ``"gpipe"`` differentiates the forward scan directly (AD through
    ppermute) and supports a ``dp_axis_name`` on a 2-axis (dp, pp) mesh.
    Stage params/opt state are the stacked ``PipelineTrainState`` form;
    microbatch inputs/targets are ``[M, mb, ...]``, replicated over pp
    (stage 0 consumes, the ring forwards) and sharded over dp if present.

    Cadence deferral composes via ``pair`` (the same program set and
    dispatcher as every other step kind). Sentinel does NOT: the health
    lane's fingerprint vote compares replicas of the same parameters, and
    pipeline stages are not replicas — engaging it here would evict
    healthy ranks for disagreeing about different weights
    (docs/train_step.md).
    """
    from ..parallel.pipeline import (pipeline_1f1b_value_and_grad,
                                     pipeline_value_and_grad)
    if schedule in ("interleaved", "1f1b"):
        if dp_axis_name is not None:
            raise ValueError(
                "the 1F1B schedule has no dp seam yet — use "
                "schedule='gpipe' with dp_axis_name, or drop the dp axis")
        vg = pipeline_1f1b_value_and_grad(stage_fn, loss_fn, axis_name)
    elif schedule == "gpipe":
        vg = pipeline_value_and_grad(stage_fn, loss_fn, axis_name,
                                     dp_axis_name=dp_axis_name)
    else:
        raise ValueError(f"unknown schedule {schedule!r}: expected "
                         "'interleaved' (alias '1f1b') or 'gpipe'")
    data_spec = P(None, dp_axis_name) if dp_axis_name else P()

    def make_program(opt, apply_update: bool):
        def sharded_step(state: PipelineTrainState, x_microbatches,
                         targets):
            def unstack(t):
                return jax.tree_util.tree_map(lambda leaf: leaf[0], t)

            def restack(t):
                return jax.tree_util.tree_map(lambda leaf: leaf[None], t)

            params = unstack(state.stage_params)
            loss, grads = vg(params, x_microbatches, targets)
            opt_state = unstack(state.opt_state)
            if apply_update:
                updates, opt_state = opt.update(grads, opt_state, params)
                with jax.named_scope("hvd_optimizer"):
                    params = optax.apply_updates(params, updates)
            return (PipelineTrainState(state.step + 1, restack(params),
                                       restack(opt_state)), loss)

        step = _shard_map(
            sharded_step, mesh=mesh,
            in_specs=(PipelineTrainState(P(), P(axis_name), P(axis_name)),
                      data_spec, data_spec),
            out_specs=(PipelineTrainState(P(), P(axis_name), P(axis_name)),
                       P()),
            check_vma=False)
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    programs = build_program_set(make_program, optimizer=optimizer,
                                 pair=pair)
    dispatch = make_dispatch(programs,
                             every=pair.every if pair is not None else 1)
    run = instrument_dispatch(dispatch, what="pipeline_train_step",
                              lower=programs["apply"].lower)
    run.lower = programs["apply"].lower
    if pair is not None:
        run.lower_apply = programs["apply"].lower
        run.lower_skip = programs["skip"].lower
    return monitored_step(run, what="pipeline_train_step")


def export_decode_params(state_or_params):
    """The training → serving export seam: the plain params pytree the
    decode path (models/decode.py) consumes.

    Accepts a train state (anything with ``.params``) or a params pytree,
    strips the optimizer state by construction, and unboxes flax
    partitioning metadata (``nn.meta.unbox``) so the serve side sees bare
    arrays — the same shape the CAS publisher stores and the registry's
    ``prepare_leaf`` re-devices. Works for both checkpoint layouts
    (unrolled ``block_i`` and scanned ``layers`` stacks); no sharding or
    donation survives the seam on purpose: serving re-places leaves on its
    own mesh.
    """
    import flax.linen as nn
    params = getattr(state_or_params, "params", state_or_params)
    if isinstance(params, dict) and "params" in params:
        params = params["params"]
    return nn.meta.unbox(params)
