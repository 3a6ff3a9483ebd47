"""Optimizer-state HBM levers for MoE expert banks (VERDICT r4 #2).

An 8-expert top-2 MoE carries an 8x-overprovisioned expert bank whose
AdamW pass is pure HBM traffic independent of batch: every step reads
grad+param+m+v and writes param+m+v for mostly-inactive weights
(12.8% of a Mixtral step in a builder's trace before PR 1; no cell of the
benchmark runs it). The three
standard levers, each expressible per-subtree so the dense params keep
exact AdamW:

- :func:`scale_by_adam_low_precision` — store m and/or v in bf16 with
  stochastic rounding (unbiased over steps; plain rounding stalls small
  accumulations).
- Adafactor-style factored second moment for the expert tensors only
  (via :func:`partition` + ``optax.adafactor``).
- :func:`every_k` — apply the expert-bank update every k-th step with
  the update scaled by k (same expected LR). CAUTION: this single-program
  ``lax.cond`` form does NOT realize the HBM saving — cond cannot alias
  loop-carried state across the branch, so the skip branch's pass-through
  of m/v/params is a COPY that measured away the entire win (and -15%
  with donation disabled; VERDICT r5 #2). For the real saving use
  :func:`deferred_pair` + ``train.make_gspmd_deferred_train_step``
  (two jitted programs; the skip program aliases donated buffers and
  DCEs the dead dL/dW einsums — +22% on Mixtral).

:func:`partition` routes subtrees to different transforms by parameter
path (``optax.multi_transform`` with a path-predicate labeler).

Reference parity: none — the reference's MoE story is the raw
``hvd.alltoall`` primitive (SURVEY §2.2); the expert-update levers are
standard MoE practice (Adafactor: Shazeer & Stern 2018; deferred expert
updates appear in large-scale MoE training systems) re-expressed as
optax transforms.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


def _cast(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def _stochastic_round(key, x, dtype):
    """Unbiased f32 -> bf16 rounding: add a uniform 16-bit value below the
    truncation point, then truncate the mantissa (bf16 = f32's top 16
    bits). E[result] = x, so tiny moment deltas accumulate in expectation
    instead of being swallowed by round-to-nearest."""
    assert dtype == jnp.bfloat16, "stochastic rounding implemented for bf16"
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, shape=x.shape, dtype=jnp.uint32) & 0xFFFF
    return jax.lax.bitcast_convert_type(
        (bits + noise) & jnp.uint32(0xFFFF0000), jnp.float32
    ).astype(jnp.bfloat16)


class ScaleByAdamLPState(NamedTuple):
    count: Any
    mu: Any
    nu: Any
    key: Any


def scale_by_adam_low_precision(b1: float = 0.9, b2: float = 0.999,
                                eps: float = 1e-8,
                                mu_dtype=None, nu_dtype=None,
                                stochastic_rounding: bool = True,
                                seed: int = 0):
    """``optax.scale_by_adam`` with the moments STORED in ``mu_dtype`` /
    ``nu_dtype`` (e.g. ``jnp.bfloat16``), computed in f32. Storing v in
    bf16 halves its HBM traffic; with ``stochastic_rounding`` the cast is
    unbiased so v's tiny per-step increments survive (plain
    round-to-nearest freezes v once ``b2*v`` dominates the update)."""

    def init(params):
        mu = _cast(jax.tree_util.tree_map(jnp.zeros_like, params), mu_dtype)
        nu = _cast(jax.tree_util.tree_map(jnp.zeros_like, params), nu_dtype)
        return ScaleByAdamLPState(jnp.zeros((), jnp.int32), mu, nu,
                                  jax.random.PRNGKey(seed))

    def _store(key, new, dtype):
        if dtype is None:
            return new
        if not stochastic_rounding or dtype != jnp.bfloat16:
            return _cast(new, dtype)
        leaves, treedef = jax.tree_util.tree_flatten(new)
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_stochastic_round(k, l, dtype)
                      for k, l in zip(keys, leaves)])

    def update(updates, state, params=None):
        del params
        count = state.count + 1
        kmu, knu, knext = jax.random.split(state.key, 3)
        f32 = jnp.float32
        mu_new = jax.tree_util.tree_map(
            lambda g, m: b1 * m.astype(f32) + (1 - b1) * g.astype(f32),
            updates, state.mu)
        nu_new = jax.tree_util.tree_map(
            lambda g, v: b2 * v.astype(f32)
            + (1 - b2) * jnp.square(g.astype(f32)),
            updates, state.nu)
        c = count.astype(f32)
        bc1 = 1 - b1 ** c
        bc2 = 1 - b2 ** c
        out = jax.tree_util.tree_map(
            lambda m, v, g: ((m / bc1) / (jnp.sqrt(v / bc2) + eps))
            .astype(g.dtype),
            mu_new, nu_new, updates)
        return out, ScaleByAdamLPState(
            count, _store(kmu, mu_new, mu_dtype),
            _store(knu, nu_new, nu_dtype), knext)

    return optax.GradientTransformation(init, update)


def adamw_low_precision(learning_rate, b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8, weight_decay: float = 1e-4,
                        mu_dtype=None, nu_dtype=None,
                        stochastic_rounding: bool = True):
    """AdamW with reduced-precision moment storage (drop-in for
    ``optax.adamw``; ``optax.adamw(mu_dtype=...)`` covers only m)."""
    return optax.chain(
        scale_by_adam_low_precision(b1, b2, eps, mu_dtype=mu_dtype,
                                    nu_dtype=nu_dtype,
                                    stochastic_rounding=stochastic_rounding),
        optax.add_decayed_weights(weight_decay),
        optax.scale_by_learning_rate(learning_rate))


class EveryKState(NamedTuple):
    count: Any
    inner: Any


def every_k(inner: optax.GradientTransformation, k: int,
            scale: Optional[float] = None):
    """Apply ``inner`` only every k-th step, scaling its update by
    ``scale`` (default k, preserving the expected per-step LR); the other
    k-1 steps emit zero updates and do NOT touch inner state. The
    applied update uses the CURRENT gradient (no accumulator: an
    accumulator would itself read+write a bank-sized buffer every step,
    spending what the deferral saves).

    PERFORMANCE CAUTION: do not expect an HBM saving from this form.
    ``lax.cond`` cannot alias the untouched m/v through the branch, so
    the skip branch COPIES the moments every step — measured to cancel
    the entire ~(k-1)/k traffic win (VERDICT r5 #2; hvd-analyze flags
    the pattern as ``jax-cond-carry``). ``every_k`` remains useful for
    SEMANTIC deferral (same expected LR with stale-free updates); for
    the real HBM/throughput win use :func:`deferred_pair` with
    ``train.make_gspmd_deferred_train_step``, which compiles separate
    apply/skip programs so donated buffers alias and the dead gradient
    einsums are DCE'd.

    CONSTRAINT: ``inner``'s internal step count only advances on apply
    steps (its state is untouched on skips), so any schedule or
    bias-correction inside it runs k-times slower than the dense params'.
    Use a CONSTANT learning rate inside ``inner`` (``moe_adamw`` enforces
    this for its ``"deferred"`` variant); Adam bias correction warming up
    k-times slower only damps the expert bank's first ~k/(1-b2) steps."""
    if k < 1:
        raise ValueError(f"every_k needs k >= 1, got {k}")
    s = float(k if scale is None else scale)

    def init(params):
        return EveryKState(jnp.zeros((), jnp.int32), inner.init(params))

    def update(updates, state, params=None):
        count = state.count + 1

        def apply(_):
            out, inner_state = inner.update(updates, state.inner, params)
            out = jax.tree_util.tree_map(lambda u: (u * s).astype(u.dtype),
                                         out)
            return out, inner_state

        def skip(_):
            zeros = jax.tree_util.tree_map(jnp.zeros_like, updates)
            return zeros, state.inner

        out, inner_state = jax.lax.cond(count % k == 0, apply, skip,
                                        operand=None)
        return out, EveryKState(count, inner_state)

    return optax.GradientTransformation(init, update)


def partition(transforms: dict,
              labeler: Callable[[str], str]) -> optax.GradientTransformation:
    """``optax.multi_transform`` keyed by parameter PATH: ``labeler``
    maps each leaf's ``/``-joined lower-cased key path to a label in
    ``transforms``."""

    def label_tree(params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        labels = []
        for path, _ in flat:
            segs = [str(getattr(p, "key", getattr(p, "name", p))).lower()
                    for p in path]
            # Under GSPMD init the params arrive flax-BOXED (nn.Partitioned
            # wraps each array, adding a 'value' path segment); at update
            # time they are unboxed. Strip the wrapper segment so the same
            # leaf gets the same label in both shapes — otherwise the
            # masked state built at init mismatches the update-time tree.
            joined = "/".join(s for s in segs if s != "value")
            labels.append(labeler(joined))
        return jax.tree_util.tree_unflatten(treedef, labels)

    return optax.multi_transform(transforms, label_tree)


def is_expert_param(path: str) -> bool:
    """The routed expert bank: ``moe/{w1,w2,w3}`` leaves (leading E dim);
    router and norms are always-active."""
    return "moe" in path and path.rsplit("/", 1)[-1] in ("w1", "w2", "w3")


def frozen_like(inner: optax.GradientTransformation):
    """Same state STRUCTURE as ``inner``, zero updates, state passed
    through untouched. The skip-program half of :func:`deferred_pair`:
    because the state is an unmodified donated jit input, XLA aliases its
    buffers to the output — zero HBM traffic — which ``lax.cond`` inside
    one program cannot do (measured: the cond form's pass-through copies
    ate the entire saving; CLAUDE.md, the ``lax.cond`` trap)."""

    def update(updates, state, params=None):
        del params
        return jax.tree_util.tree_map(jnp.zeros_like, updates), state

    return optax.GradientTransformation(inner.init, update)


class DeferredPair(NamedTuple):
    """A matched (apply, skip) optimizer pair plus its cadence — one
    value, so the update scale (baked into ``apply``) and the dispatch
    cadence (consumed by ``train.make_gspmd_deferred_train_step``) can
    never disagree."""
    apply: Any
    skip: Any
    every: int


def deferred_pair(learning_rate, *, every: int = 4,
                  weight_decay: float = 1e-4, b1: float = 0.9,
                  b2: float = 0.999, eps: float = 1e-8,
                  expert_nu_dtype=None,
                  is_expert: Callable[[str], bool] = is_expert_param):
    """TWO-program expert-update deferral: returns a :class:`DeferredPair`
    of optimizers with identical state structure. Compile each into its
    own jitted step with donation (``train.make_gspmd_deferred_train_
    step``); the skip program's expert param/m/v alias straight through
    (zero optimizer HBM for the bank on k-1 of k steps) while the apply
    program applies the ``every``-scaled AdamW update from the current
    gradient. Constant LR only (same constraint as :func:`every_k`).
    ``expert_nu_dtype=jnp.bfloat16`` stacks the reduced-precision second
    moment on the apply program."""
    if callable(learning_rate):
        raise ValueError("deferred_pair needs a constant learning rate "
                         "(the expert arm ticks only on apply steps)")
    dense = optax.adamw(learning_rate, b1=b1, b2=b2, eps=eps,
                        weight_decay=weight_decay)
    if expert_nu_dtype is not None:
        expert_inner = adamw_low_precision(
            learning_rate, b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, nu_dtype=expert_nu_dtype)
    else:
        expert_inner = optax.adamw(learning_rate, b1=b1, b2=b2, eps=eps,
                                   weight_decay=weight_decay)
    expert_apply = optax.chain(expert_inner, optax.scale(float(every)))
    labeler = (lambda p: "expert" if is_expert(p) else "dense")
    opt_apply = partition({"dense": dense, "expert": expert_apply}, labeler)
    opt_skip = partition({"dense": dense,
                          "expert": frozen_like(expert_apply)}, labeler)
    return DeferredPair(opt_apply, opt_skip, every)


def moe_adamw(learning_rate, *, expert_variant: str = "adamw",
              weight_decay: float = 1e-4, b1: float = 0.9, b2: float = 0.999,
              eps: float = 1e-8, every: int = 4,
              is_expert: Callable[[str], bool] = is_expert_param):
    """AdamW with a selectable treatment for the expert bank (dense params
    always get exact AdamW):

    - ``"adamw"``      exact AdamW everywhere (baseline)
    - ``"bf16_nu"``    expert v stored bf16 + stochastic rounding
    - ``"bf16_munu"``  expert m AND v stored bf16 + stochastic rounding
    - ``"factored"``   Adafactor for expert tensors (factored v, no m)
    - ``"deferred"``   expert update applied every ``every`` steps at
                       ``every``-scaled LR, skipped (zero HBM) otherwise
    """
    dense = optax.adamw(learning_rate, b1=b1, b2=b2, eps=eps,
                        weight_decay=weight_decay)
    if expert_variant == "adamw":
        return dense
    if expert_variant == "bf16_nu":
        expert = adamw_low_precision(learning_rate, b1=b1, b2=b2, eps=eps,
                                     weight_decay=weight_decay,
                                     nu_dtype=jnp.bfloat16)
    elif expert_variant == "bf16_munu":
        expert = adamw_low_precision(learning_rate, b1=b1, b2=b2, eps=eps,
                                     weight_decay=weight_decay,
                                     mu_dtype=jnp.bfloat16,
                                     nu_dtype=jnp.bfloat16)
    elif expert_variant == "factored":
        expert = optax.adafactor(learning_rate, decay_rate=b2,
                                 weight_decay_rate=weight_decay)
    elif expert_variant == "deferred":
        if callable(learning_rate):
            # every_k only ticks the inner transform on apply steps, so a
            # schedule inside it would advance k-times slower than the
            # dense params' — silently diverging LRs (r5 review).
            raise ValueError(
                "expert_variant='deferred' needs a constant learning rate "
                "(the deferred inner AdamW's schedule count advances only "
                "every k steps; see every_k's docstring)")
        expert = every_k(dense, every)
    else:
        raise ValueError(f"unknown expert_variant {expert_variant!r}")
    return partition({"dense": dense, "expert": expert},
                     lambda p: "expert" if is_expert(p) else "dense")
