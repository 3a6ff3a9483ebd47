"""GSPMD path: multi-axis (dp/fsdp/sp/tp/ep) training by sharding annotation.

The shard_map path (``train/dp.py``) is the hvd-parity explicit-collective
design (DP only, like the reference). For tensor/sequence/expert parallelism
the TPU-idiomatic route is GSPMD: params carry logical axis names
(models/llama.py LOGICAL_RULES), activations carry constraints, and XLA
inserts every collective — including the DP gradient psum the reference
needed its whole runtime for. Use a PLAIN optax optimizer here (not
``optimizer.distributed``): the grad sync is implicit in the sharding.

Program assembly (apply/skip/probe), host dispatch (cadence + sentinel)
and scan/accumulation folding are the shared ``step_builder`` machinery
(docs/train_step.md); this module only describes the annotated loss/update
body.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax.linen import partitioning as nn_partitioning
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import sentinel as _sentinel
from ..core.watchdog import monitored_step
from .step_builder import (accumulate_gradients, build_program_set,
                           fold_scan, instrument_dispatch, make_dispatch)


class GSPMDTrainState(NamedTuple):
    step: Any
    params: Any
    opt_state: Any


def next_token_loss(logits, tokens, mask=None):
    """Shifted next-token cross entropy (standard LM objective).

    Written as ``logsumexp - target_logit`` rather than materializing the
    full ``log_softmax`` tensor: at LM-head sizes the [B,T,V] f32
    log-probs cost an extra HBM write+read per step for values that are
    immediately reduced away (profile_mixtral.py, r4)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if mask is not None:
        m = mask[:, 1:].astype(nll.dtype)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


def rules_for_mesh(mesh, rules):
    """Drop mesh axes a rule names that this mesh doesn't have, so one rule
    table serves any mesh shape (dp-only, dp×tp, dp×fsdp×sp×tp, ...)."""
    out = []
    for logical, target in rules:
        if target is None:
            out.append((logical, None))
            continue
        t = target if isinstance(target, tuple) else (target,)
        t = tuple(a for a in t if a in mesh.axis_names)
        out.append((logical, t if len(t) > 1 else (t[0] if t else None)))
    return tuple(out)


def gspmd_shardings(model, optimizer, rng, sample_tokens, mesh, rules):
    """Abstract-init the model and derive NamedShardings for params and
    optimizer state from the logical annotations."""
    rules = rules_for_mesh(mesh, rules)
    with nn_partitioning.axis_rules(rules):
        abs_vars = jax.eval_shape(model.init, rng, sample_tokens)
    abs_params = abs_vars["params"]
    abs_opt = jax.eval_shape(optimizer.init, abs_params)
    param_sharding = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abs_params), mesh, rules)
    opt_sharding = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abs_opt), mesh, rules)

    def _fit_rank(sh, leaf):
        # Rank-CHANGING optimizer states (Adafactor's factored v_row/v_col,
        # SM3 diagonals, ...) inherit the full param's axis names from the
        # flax box; a spec longer than the leaf's rank is invalid — store
        # those small reduced moments replicated instead.
        ndim = getattr(leaf, "ndim", None)
        if ndim is None:
            # the spec tree's leaf pairs with a still-BOXED abs subtree
            # (nn.Partitioned around one ShapeDtypeStruct)
            inner = jax.tree_util.tree_leaves(leaf)
            ndim = getattr(inner[0], "ndim", None) if len(inner) == 1 \
                else None
        if ndim is not None and isinstance(sh, NamedSharding) \
                and len(sh.spec) > ndim:
            return NamedSharding(mesh, P())
        return sh

    opt_sharding = jax.tree_util.tree_map(_fit_rank, opt_sharding, abs_opt)
    return param_sharding, opt_sharding


def create_gspmd_train_state(model, optimizer, rng, sample_tokens, mesh,
                             rules) -> GSPMDTrainState:
    """Initialise params/opt state already laid out per the rule table."""
    param_sharding, opt_sharding = gspmd_shardings(
        model, optimizer, rng, sample_tokens, mesh, rules)
    rules = rules_for_mesh(mesh, rules)

    def init_all(rng, sample):
        with nn_partitioning.axis_rules(rules):
            variables = model.init(rng, sample)
        params = variables["params"]
        return params, optimizer.init(params)

    with jax.sharding.set_mesh(mesh):
        params, opt_state = jax.jit(
            init_all, out_shardings=(param_sharding, opt_sharding))(
                rng, sample_tokens)
    params = nn.meta.unbox(params)
    opt_state = nn.meta.unbox(opt_state)
    return GSPMDTrainState(jnp.zeros((), jnp.int32), params, opt_state)


def _build_gspmd_step(model, mesh, rules, *, optimizer=None, pair=None,
                      loss_fn: Callable = None,
                      data_axes=("dp", "fsdp"), seq_axis: str = "sp",
                      donate: bool = True, aux_weight: float = 0.0,
                      scan_steps: Optional[int] = None,
                      accum_steps: Optional[int] = None,
                      sentinel=None):
    """Shared GSPMD step assembly: one annotated body factory handed to
    ``step_builder.build_program_set``, one ``make_dispatch`` over the
    resulting apply/skip/probe set. ``make_gspmd_train_step`` (optimizer,
    no cadence) and ``make_gspmd_deferred_train_step`` (``pair`` cadence)
    are thin entries into this."""
    # Resolve the sentinel ONCE so all programs share a single policy
    # object — two ladders independently counting the same bad steps must
    # not happen. Env-default engagement (HOROVOD_SENTINEL=1 with no
    # explicit kwarg) is pinned here for the same reason.
    sentinel = _sentinel.resolve(sentinel)
    loss_fn = loss_fn or next_token_loss
    rules = rules_for_mesh(mesh, rules)
    present = [a for a in data_axes if a in mesh.axis_names]
    seq = seq_axis if seq_axis in mesh.axis_names else None
    token_sharding = NamedSharding(mesh, P(tuple(present) or None, seq))

    def make_step(opt, apply_update: bool):
        # Probe variant (apply_update=False): optimizer.update is never
        # traced, donated state aliases through, update work is DCE'd —
        # the step_builder two-program trick shared with the cadence
        # skip program.
        def step(state: GSPMDTrainState, tokens):
            tokens = jax.lax.with_sharding_constraint(tokens,
                                                      token_sharding)

            def run_grads(params, toks):
                with nn_partitioning.axis_rules(rules), \
                        jax.named_scope("hvd_forward"):
                    logits, mods = model.apply({"params": params}, toks,
                                               mutable=["losses"])
                    loss = loss_fn(logits, toks)
                if aux_weight and "losses" in mods:
                    aux = sum(jnp.sum(v) for v in
                              jax.tree_util.tree_leaves(mods["losses"]))
                    loss = loss + aux_weight * aux
                return loss

            vg = jax.value_and_grad(run_grads)
            if accum_steps is not None and accum_steps > 1:
                def acc_vg(params, aux, toks):
                    loss, grads = vg(params, toks)
                    return (loss, aux), grads
                (loss, _), grads = accumulate_gradients(
                    acc_vg, state.params, (), (tokens,), accum_steps)
            else:
                loss, grads = vg(state.params, tokens)
            health = None
            if sentinel is not None:
                health = _sentinel.health_vector(grads, state.params)
            if apply_update:
                updates, opt_state = opt.update(grads, state.opt_state,
                                                state.params)
                with jax.named_scope("hvd_optimizer"):
                    params = optax.apply_updates(state.params, updates)
                if sentinel is not None:
                    ok = health[:, 0].min() >= 1.0

                    def guard(new, old):
                        return jnp.where(ok, new, old)
                    params = jax.tree_util.tree_map(guard, params,
                                                    state.params)
                    opt_state = jax.tree_util.tree_map(guard, opt_state,
                                                       state.opt_state)
            else:
                params, opt_state = state.params, state.opt_state
            out_state = GSPMDTrainState(state.step + 1, params, opt_state)
            if sentinel is not None:
                return out_state, loss, health
            return out_state, loss

        if scan_steps is not None:
            step = fold_scan(step, scan_steps, sentinel is not None)
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    programs = build_program_set(make_step, optimizer=optimizer, pair=pair,
                                 sentinel=sentinel)
    inner = make_dispatch(programs, sentinel=sentinel,
                          every=pair.every if pair is not None else 1,
                          scan_steps=scan_steps)

    def in_mesh(state, tokens):
        if jax.sharding.get_abstract_mesh() == mesh.abstract_mesh:
            # already ambient (a caller's own set_mesh, or hvd-analyze
            # tracing this step: jax forbids set_mesh inside a trace)
            return inner(state, tokens)
        with jax.sharding.set_mesh(mesh):
            return inner(state, tokens)

    def _mesh_lower(prog):
        def lower(state, tokens):
            # AOT introspection must trace under the SAME mesh the step
            # executes with (tests/test_contracts.py compares the
            # post-SPMD-partitioning collective HLO of two such lowerings).
            with jax.sharding.set_mesh(mesh):
                return prog.lower(state, tokens)
        return lower

    lower = _mesh_lower(programs["apply"])
    run = instrument_dispatch(in_mesh, what="gspmd_train_step", lower=lower)
    run.lower = lower
    if sentinel is not None:
        run.lower_probe = _mesh_lower(programs["probe"])
        run.sentinel = sentinel
    if pair is not None:
        # Per-program AOT handles (the dispatcher itself has no single
        # lowering): tests/test_contracts.py pins that at every=1 the
        # apply program's collective HLO is byte-identical to the
        # standard step's.
        run.lower_apply = lower
        run.lower_skip = _mesh_lower(programs["skip"])
    return monitored_step(run, what="gspmd_train_step")


def make_gspmd_train_step(model, optimizer, mesh, rules, *,
                          loss_fn: Callable = None,
                          data_axes=("dp", "fsdp"), seq_axis: str = "sp",
                          donate: bool = True, aux_weight: float = 0.0,
                          scan_steps: Optional[int] = None,
                          accum_steps: Optional[int] = None,
                          sentinel=None):
    """Jitted LM train step: ``step(state, tokens) -> (state, loss)``.
    ``tokens`` [B, T] is sharded batch-over-data-axes, seq-over-sp; all
    tp/sp/ep/fsdp collectives AND the dp grad psum are inserted by XLA from
    the sharding annotations.

    ``scan_steps``/``accum_steps`` fold/microbatch exactly as in
    :func:`~horovod_tpu.train.dp.make_train_step` (the shared
    ``step_builder`` machinery); with accumulation the implicit XLA grad
    reductions fire once on the accumulated gradients, after the loop.

    ``sentinel`` engages the numeric-integrity ladder exactly as in
    :func:`~horovod_tpu.train.dp.make_train_step`. GSPMD has no named rank
    axis, so the health vector is the ``[1, 3]`` global form (global
    finiteness/norm/digest via XLA's implicit reductions): skip and
    rollback work; per-rank fingerprint eviction needs the shard_map DP
    step."""
    return _build_gspmd_step(model, mesh, rules, optimizer=optimizer,
                             loss_fn=loss_fn, data_axes=data_axes,
                             seq_axis=seq_axis, donate=donate,
                             aux_weight=aux_weight, scan_steps=scan_steps,
                             accum_steps=accum_steps, sentinel=sentinel)


def make_gspmd_deferred_train_step(model, pair, mesh, rules, **kw):
    """Two-PROGRAM expert-update deferral: ``pair`` is the
    ``optimizer.deferred_pair`` result (apply/skip optimizers + cadence
    in ONE value, so the k baked into the apply program's update scale
    and the k used for dispatch cannot disagree). Compiles one step per
    optimizer and dispatches by a host-side step counter — k-1 skip
    steps, then one apply step. The skip program's untouched expert
    param/m/v are donated jit inputs returned unchanged, so XLA aliases
    their buffers (zero optimizer HBM for the bank) AND dead-code-
    eliminates the bank's dL/dW einsums (their only consumer was the
    skipped update) — which a ``lax.cond`` inside ONE program cannot
    achieve (its pass-through copies measured the saving away; CLAUDE.md,
    the ``lax.cond`` trap). Both optimizers share a state structure;
    init with ``pair.apply``. Requires ``donate=True`` (the default)
    for the aliasing to exist.

    Composes with ``sentinel`` through the shared dispatcher: ONE policy
    ladder, and ONE probe program shared by both cadence phases (the
    probe never traces either optimizer's update, so it is the same
    program regardless of phase) — three jitted programs total, not four.
    """
    return _build_gspmd_step(model, mesh, rules, pair=pair, **kw)
