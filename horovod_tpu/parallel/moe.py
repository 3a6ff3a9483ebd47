"""Expert parallelism: capacity-based MoE dispatch over an all_to_all axis.

Reference parity (SURVEY.md §2.6): the reference ships the ``alltoall``
primitive (nccl_operations.cc AllToAll, MPI_Alltoallv) but no MoE layer or
router — EP is "primitive only". BASELINE.md config 4 (Mixtral-8x7B) demands
the full path, built here the TPU way:

- tokens are routed top-k with a capacity limit. Two dispatch forms:
  the GShard-style one-hot einsum router (``topk_router``, kept as the
  readable reference + parity oracle) and the production sort-based
  GATHER-ONLY plan (``topk_router_sorted`` — all static shapes, zero
  scatters even in backward; see its docstring for why);
- experts are sharded over the ``ep`` mesh axis; the token exchange is ONE
  ``lax.all_to_all`` each way over ICI (the exact op the reference exposes
  but can only run host-side, here fused into the compiled graph);
- the combine applies router probabilities on the way back.

All functions run inside ``shard_map`` over the ep axis.

A second, DROPLESS form (``dropless_share`` and the functions above it, end
of this file) serves sigmoid-routed layers with many small experts: no
capacity, a selection bias that no gradient trains, and a rank that is told
which experts it holds and computes their part of the layer's sum only,
over the rows that the step filled and not over the plan's bound.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..core import telemetry as _telemetry


class RouterOutput(NamedTuple):
    dispatch: jnp.ndarray   # [T, E, C] one-hot routing tensor
    combine: jnp.ndarray    # [T, E, C] probability-weighted combine tensor
    aux_loss: jnp.ndarray   # load-balancing auxiliary loss (scalar)


def topk_router(router_logits, num_experts: int, capacity: int,
                top_k: int = 2) -> RouterOutput:
    """GShard-style top-k router with per-expert capacity.

    Tokens beyond an expert's capacity are dropped (standard behavior;
    combine weight 0 → they pass through the residual path).
    """
    T = router_logits.shape[0]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    # aux loss (Switch eq. 4): E * mean(frac_tokens * frac_probs)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, num_experts, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum(frac_tokens * frac_probs)

    dispatch = jnp.zeros((T, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((T, num_experts, capacity), jnp.float32)
    # claimed positions per expert accumulate across the k choices
    base_count = jnp.zeros((num_experts,), jnp.int32)
    p_rem = probs
    for _ in range(top_k):
        choice = jnp.argmax(p_rem, axis=-1)                   # [T]
        gate = jnp.take_along_axis(p_rem, choice[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(choice, num_experts, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1 + base_count[None, :]
        pos_in_choice = jnp.take_along_axis(pos, choice[:, None], 1)[:, 0]
        keep = pos_in_choice < capacity
        d = (jax.nn.one_hot(choice, num_experts, dtype=jnp.float32)
             [:, :, None] *
             jax.nn.one_hot(jnp.clip(pos_in_choice, 0, capacity - 1),
                            capacity, dtype=jnp.float32)[:, None, :])
        d = d * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        base_count = base_count + jnp.sum(onehot, axis=0)
        p_rem = p_rem * (1.0 - jax.nn.one_hot(choice, num_experts,
                                              dtype=jnp.float32))
    # renormalise combine weights over the selected experts (Mixtral style)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return RouterOutput(dispatch, combine, aux_loss)


class SortedRouting(NamedTuple):
    """Sort-based routing plan (no [T,E,C] one-hot tensors).

    ``k*T`` flattened (round, token) entries in ROUND-MAJOR order
    (index = round*T + token), matching :func:`topk_router`'s claim
    priority (all first choices claim capacity before any second
    choice). Carries BOTH directions of the token<->slot mapping so
    dispatch and combine — and, via their custom VJPs, both backward
    passes — are pure row GATHERS: TPU scatters serialize row updates
    and profiled as slow as the one-hot einsums they replaced
    (profile_mixtral.py, r4).
    """
    token_idx: jnp.ndarray   # [k*T] int32: source token of each entry
    dest: jnp.ndarray        # [k*T] int32: expert*capacity + slot, or
    #                          E*capacity (out-of-range sentinel) if dropped
    weight: jnp.ndarray      # [k*T] f32: renormalized gate (0 if dropped)
    slot_entry: jnp.ndarray  # [E*C] int32: entry filling each slot (clipped)
    slot_valid: jnp.ndarray  # [E*C] bool: slot actually claimed
    aux_loss: jnp.ndarray    # same load-balancing loss as topk_router


def topk_router_sorted(router_logits, num_experts: int, capacity: int,
                       top_k: int = 2) -> SortedRouting:
    """Top-k router producing a gather-based dispatch plan.

    Numerically equivalent to :func:`topk_router` (same expert choices,
    same capacity-claim priority, same renormalized combine weights,
    same aux loss) but O(k·T·D) memory traffic instead of materializing
    two [T, E, C] one-hot tensors and O(T·E·C·D) dispatch einsums — at
    the Mixtral bench config those einsums cost MORE device time than
    the expert matmuls themselves (profile_mixtral.py, r4).
    """
    T = router_logits.shape[0]
    kT = top_k * T
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, num_experts, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum(frac_tokens * frac_probs)

    gate, choice = lax.top_k(probs, top_k)            # [T, k]
    # round-major flatten: entry r*T + t  (claim priority = round, token)
    e_flat = choice.T.reshape(-1).astype(jnp.int32)   # [k*T]
    g_flat = gate.T.reshape(-1)
    token_idx = jnp.tile(jnp.arange(T, dtype=jnp.int32), top_k)

    # stable sort by expert: within an expert, entries keep round-major
    # order — exactly topk_router's base_count claim sequence
    order = jnp.argsort(e_flat, stable=True).astype(jnp.int32)
    e_sorted = e_flat[order]
    counts = jnp.sum(jax.nn.one_hot(e_flat, num_experts, dtype=jnp.int32),
                     axis=0)                          # [E]
    start = jnp.cumsum(counts) - counts               # exclusive cumsum
    pos = jnp.arange(kT, dtype=jnp.int32) - start[e_sorted]
    keep_sorted = pos < capacity
    dest_sorted = jnp.where(
        keep_sorted, e_sorted * capacity + jnp.minimum(pos, capacity - 1),
        num_experts * capacity)                       # sentinel = dropped
    # un-sort back to (round, token) order — a tiny int permutation
    # scatter ([k*T] elements), nothing row-sized
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(kT, dtype=jnp.int32))
    dest = dest_sorted[inv]
    kept = g_flat * (dest < num_experts * capacity)
    # round-major layout: entries of token t sit at {r*T + t} — the
    # per-token reduction is a reshape-sum, not a segment scatter
    denom = kept.reshape(top_k, T).sum(0)
    weight = kept / jnp.maximum(denom, 1e-9)[token_idx]

    # slot-side view: slot (e, p) is filled by sorted entry start[e]+p
    grid = (start[:, None] + jnp.arange(capacity, dtype=jnp.int32)[None, :]
            ).reshape(-1)                             # [E*C]
    slot_valid = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                  < jnp.minimum(counts, capacity)[:, None]).reshape(-1)
    slot_entry = order[jnp.clip(grid, 0, kT - 1)]
    return SortedRouting(token_idx, dest, weight, slot_entry, slot_valid,
                         aux_loss)


from functools import partial as _partial


def _zero_tan(a):
    """float0 zero-cotangent for integer/bool plan arrays (the jax
    convention for non-differentiable array inputs of a custom_vjp)."""
    import numpy as _np
    from jax.dtypes import float0
    return _np.zeros(a.shape, float0)


@_partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch_rows(x, slot_entry, slot_valid, dest, top_k: int):
    """buf[s] = x[token(slot_entry[s])] * valid[s] — gather only."""
    T = x.shape[0]
    rows = x[slot_entry % T]
    return rows * slot_valid[:, None].astype(x.dtype)


def _dispatch_rows_fwd(x, slot_entry, slot_valid, dest, top_k):
    return _dispatch_rows(x, slot_entry, slot_valid, dest, top_k), \
        (x.shape[0], slot_entry, slot_valid, dest)


def _dispatch_rows_bwd(top_k, res, dbuf):
    # dx[t] = sum_r dbuf[dest[r*T + t]] — ALSO a gather (+ reshape-sum):
    # the mirror of the combine forward, so no scatter in the transpose.
    T, slot_entry, slot_valid, dest = res
    rows = dbuf.at[dest].get(mode="fill", fill_value=0)
    dx = rows.reshape(top_k, T, -1).sum(0)
    return dx, _zero_tan(slot_entry), _zero_tan(slot_valid), _zero_tan(dest)


def sorted_dispatch(x, r: SortedRouting, num_experts: int, capacity: int):
    """[T, D] tokens -> [E, C, D] expert buffers, gathers only (fwd AND
    bwd — see :class:`SortedRouting`). Unclaimed capacity slots are
    zero, as with the one-hot dispatch."""
    k = r.dest.shape[0] // x.shape[0]
    buf = _dispatch_rows(x, r.slot_entry, r.slot_valid, r.dest, k)
    return buf.reshape(num_experts, capacity, x.shape[-1])


@_partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine_rows(flat, weight, dest, slot_entry, slot_valid,
                  num_tokens: int):
    """y[t] = sum_r flat[dest[r*T+t]] * weight[r*T+t] — gather only."""
    rows = flat.at[dest].get(mode="fill", fill_value=0)
    k = dest.shape[0] // num_tokens
    return (rows.reshape(k, num_tokens, -1)
            * weight.reshape(k, num_tokens, 1)).sum(0)


def _combine_rows_fwd(flat, weight, dest, slot_entry, slot_valid,
                      num_tokens):
    y = _combine_rows(flat, weight, dest, slot_entry, slot_valid,
                      num_tokens)
    return y, (flat, weight, dest, slot_entry, slot_valid)


def _combine_rows_bwd(num_tokens, res, dy):
    # dflat[s] = dy[token(slot_entry[s])] * weight[slot_entry[s]] * valid
    # — gathers; dweight[j] = <dy[token(j)], flat[dest[j]]> — gathers.
    flat, weight, dest, slot_entry, slot_valid = res
    T = num_tokens
    w_slot = weight[slot_entry] * slot_valid
    dflat = (dy[slot_entry % T] * w_slot[:, None]).astype(flat.dtype)
    rows = flat.at[dest].get(mode="fill", fill_value=0)
    k = dest.shape[0] // T
    dweight = jnp.sum(rows.reshape(k, T, -1)
                      * dy.reshape(1, T, -1), axis=-1).reshape(-1)
    return (dflat, dweight, _zero_tan(dest), _zero_tan(slot_entry),
            _zero_tan(slot_valid))


def sorted_combine(out, r: SortedRouting, num_tokens: int):
    """[E, C, D] expert outputs -> [T, D] weighted combine, gathers only
    (fwd AND bwd). Accumulates in f32 like the one-hot combine."""
    E, C, D = out.shape
    flat = out.reshape(E * C, D).astype(jnp.float32)
    y = _combine_rows(flat, r.weight, r.dest, r.slot_entry, r.slot_valid,
                      num_tokens)
    return y.astype(out.dtype)


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)
_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def expert_alltoall(expert_inputs, axis_name: str):
    """[E, C, D] (all experts' buffers on this device) -> [E_local, n*C, D]
    (this device's experts, tokens from every device). One all_to_all."""
    n = lax.axis_size(axis_name)
    E, C, D = expert_inputs.shape
    if E % n:
        raise ValueError(f"experts {E} not divisible by ep axis size {n}")
    x = lax.all_to_all(expert_inputs, axis_name, split_axis=0, concat_axis=1,
                       tiled=True)  # [E/n, n*C, D]
    return x


def expert_alltoall_back(expert_outputs, axis_name: str):
    """Inverse of :func:`expert_alltoall`: [E_local, n*C, D] -> [E, C, D]."""
    return lax.all_to_all(expert_outputs, axis_name, split_axis=1,
                          concat_axis=0, tiled=True)


def routed_experts(x, router_logits, expert_fn: Callable, *,
                   axis_name: Optional[str], num_experts: int,
                   capacity_factor: float = 1.25, top_k: int = 2,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full MoE layer body: route → all_to_all → experts → all_to_all → combine.

    x: [T, D] local tokens; router_logits: [T, E];
    ``expert_fn(expert_inputs)`` maps [E_local, tokens, D] -> same (vmapped
    per-expert weights live in the caller's closure).
    Returns (output [T, D], aux_loss scalar).
    With ``axis_name=None`` runs single-device (all experts local).
    """
    T, D = x.shape
    n = lax.axis_size(axis_name) if axis_name else 1
    capacity = max(1, int(capacity_factor * top_k * T / num_experts))
    r = topk_router_sorted(router_logits, num_experts, capacity, top_k)
    dispatched = sorted_dispatch(x, r, num_experts, capacity)  # [E,C,D]
    if axis_name:
        dispatched = expert_alltoall(dispatched, axis_name)  # [E/n, n*C, D]
    out = expert_fn(dispatched)
    if axis_name:
        out = expert_alltoall_back(out, axis_name)           # [E, C, D]
    y = sorted_combine(out, r, T)
    return y, r.aux_loss


# --------------------------------------------------------------------------
# Dropless routing for ONE SHARE of the experts (sigmoid scores, a selection
# bias that no gradient trains, nothing dropped). The router keeps every
# expert's score and every token's k choices; this rank holds experts
# ``[first, first + held)`` and computes their part of the layer's sum. What
# the absent experts would add is left out: on more ranks it arrives through
# the ``ep`` exchange, which this single-rank form runs without.

# The name (``jax.ad_checkpoint.checkpoint_name``) of a layer's route: the
# choices, the chosen scores, the gates and the plan. A rematerialisation
# policy that saves it (``models/llama.py`` "dots_attn") hands the backward
# the forward's route.
ROUTE = "moe_route"
# The name of the small plan's rows and its three grouped products
# (``a = rows w1``, ``b = rows w3``, ``out = silu(a) b w2``). A policy that
# saves it ("dots_attn") hands them to the window's transpose, which then
# runs no product forward again.
PRODUCTS = "moe_products"


class HeldRouting(NamedTuple):
    """Plan for the (token, choice) pairs that land on held experts, sorted
    by expert. ``k*T`` rows is the static bound (every pair could land
    here); ``group_sizes`` says how many are filled, at the front, and
    :func:`dropless_share` works over windows of the plan that hold them
    (:func:`_window`). Both directions of the mapping
    are kept so that dispatch and combine, forward and backward, are row
    gathers (see :class:`SortedRouting` for why not scatters of ``k*T``
    rows; a window of few rows is scattered, :func:`_row_sums`)."""
    row_token: jnp.ndarray    # [k*T] int32: source token of each sorted row
    row_entry: jnp.ndarray    # [k*T] int32: its (token*k + choice) pair
    dest: jnp.ndarray         # [T, k] int32: row of each pair (if ``on``)
    on: jnp.ndarray           # [T, k] bool: the pair's expert is held here
    valid: jnp.ndarray        # [k*T] bool: the row is filled
    group_sizes: jnp.ndarray  # [held] int32: rows of each held expert


@_partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chosen(scores, choice, experts: int):
    """``scores[t, choice[t, j]]`` by a select over the ``experts``: one
    term and zeros a sum, so exact, where a gather of the k*T scores took
    0.84 ms a layer on a v5e. The transpose is the gather's, a scatter-add
    of the k*T cotangents (0.014 ms a layer there; the select's own, a sum
    over the choices of ``[T, k, experts]``, 0.15)."""
    chosen = choice[..., None] == jnp.arange(experts)
    return jnp.where(chosen, scores[..., None, :], 0).sum(-1)


def _chosen_fwd(scores, choice, experts):
    return _chosen(scores, choice, experts), choice


def _chosen_bwd(experts, choice, dpicked):
    rows = jnp.arange(choice.shape[0])[:, None]
    dscores = jnp.zeros((choice.shape[0], experts), dpicked.dtype)
    return dscores.at[rows, choice].add(dpicked), _zero_tan(choice)


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def kept_groups(biased, n_group: int, topk_group: int):
    """``biased [T, E]`` with every expert outside a token's ``topk_group``
    best groups at ``-inf``: the experts fall in ``n_group`` groups of
    consecutive indices, and a group's score is the sum of its two largest
    values (DeepSeek-V3's ``noaux_tc`` group step)."""
    T, E = biased.shape
    grouped = biased.reshape(T, n_group, E // n_group)
    best = lax.top_k(grouped, 2)[0].sum(-1)                  # [T, n_group]
    kept = lax.top_k(best, topk_group)[1]                    # [T, topk_group]
    keep = (kept[..., None] == jnp.arange(n_group)).any(-2)  # [T, n_group]
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(T, E)


def sigmoid_topk_gates(logits, bias, top_k: int, scaling: float,
                       n_group: int = 1, topk_group: int = 1):
    """DeepSeek-V3's ``noaux_tc`` selection: float32 sigmoid scores, the
    ``top_k`` of ``score + bias`` chosen (with ``n_group > 1``, among the
    experts of the ``topk_group`` best groups alone, :func:`kept_groups`),
    gates the chosen scores renormalised over the choice and scaled. The
    bias picks and does not weigh, and carries no gradient. Returns
    ``(choice [T, k] int32, gates [T, k] f32)``. The choice, the chosen
    scores and the gates are named :data:`ROUTE` as they are made, so that
    a rematerialisation that keeps the route leaves the gates' gradient
    neither a ``top_k`` nor a select to make again."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    biased = scores + lax.stop_gradient(bias)
    if n_group > 1:
        biased = kept_groups(biased, n_group, topk_group)
    _, choice = lax.top_k(biased, top_k)
    choice = checkpoint_name(choice.astype(jnp.int32), ROUTE)
    # merged with the select's sum over the experts, XLA would add the
    # denominator's k terms in another order, and round the gates otherwise
    picked = lax.optimization_barrier(
        _chosen(scores, choice, scores.shape[-1]))
    picked = checkpoint_name(picked, ROUTE)
    gates = scaling * picked / picked.sum(-1, keepdims=True)
    return choice, checkpoint_name(gates, ROUTE)


def held_plan(choice, first: int, held: int) -> HeldRouting:
    """The pairs whose expert is in ``[first, first + held)`` in rows by
    expert, each expert's in pair order; every other pair lies behind them
    and fills no row. Row -> pair is a stable sort of the pairs by expert.
    Pair -> row and the experts' rows are counted: a running count of each
    of the ``held + 1`` classes along the pairs gives a pair its rank in its
    class, its row is the rows of the classes before it plus that rank, and
    the last count is each class's size. Integer sums, so the rows are the
    sort's; they take the place of a k*T scatter of the sort's permutation
    (on a v5e at k*T = 81,920 the sort took 0.08 ms a layer and that
    scatter 0.38)."""
    T, k = choice.shape
    local = choice - first
    on = (local >= 0) & (local < held)
    key = jnp.where(on, local, held).reshape(-1)            # [k*T]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    hot = key == jnp.arange(held + 1, dtype=jnp.int32)[:, None]
    seen = jnp.cumsum(hot, axis=1, dtype=jnp.int32)         # [held+1, k*T]
    sizes = seen[:, -1]
    before = jnp.cumsum(sizes) - sizes
    # pair -> row, a select over the classes: nothing row-sized, no scatter
    dest = jnp.sum(jnp.where(hot, before[:, None] + seen - 1, 0), axis=0)
    group_sizes = sizes[:held]
    rows = jnp.arange(k * T, dtype=jnp.int32)
    return HeldRouting(order // k, order, dest.reshape(T, k), on,
                       rows < group_sizes.sum(), group_sizes)


def _sums_by_rows(n: int, pairs: int) -> bool:
    """Whether a window of ``n`` rows brings its rows to the tokens row by
    row (:func:`_row_sums`) or pair by pair (:func:`_pair_sums`). The pair
    form writes and reads ``pairs`` rows of which ``n`` at most are held;
    the row form costs by the row, about a fifth of a microsecond each on a
    v5e, and is ahead where the window is a sixteenth of the pairs and
    behind at an eighth (8 choices of 2048-wide rows)."""
    return 16 * n <= pairs


def _pair_sums(rows, gates, plan: HeldRouting):
    """``y[t] = sum over t's held pairs of gates[t, c] * rows[dest[t, c]]``
    in float32 (``gates`` None: the rows as they are): a ``[T, k, D]``
    gather, masked and summed over ``k``."""
    picked = jnp.where(plan.on[..., None], rows[plan.dest], 0)
    picked = picked.astype(jnp.float32)
    if gates is not None:
        picked = picked * gates[..., None]
    return picked.sum(1)


def _row_sums(rows, gates, plan: HeldRouting):
    """:func:`_pair_sums` from the rows' side: each filled row weighed by
    its own pair's gate and added to its token's sum, a scatter-add of ``n``
    float32 rows (a scatter of all ``k*T`` would serialize sixteen times as
    many). The order of a token's terms is the scatter's."""
    rows = rows.astype(jnp.float32)
    if gates is not None:
        rows = rows * gates.reshape(-1)[plan.row_entry][:, None]
    # what lies past the filled rows is undefined in a cotangent
    rows = jnp.where(plan.valid[:, None], rows, 0)
    sums = jnp.zeros((plan.on.shape[0], rows.shape[1]), jnp.float32)
    return sums.at[plan.row_token].add(rows)


def _token_sums(rows, gates, plan: HeldRouting):
    """The window's rows summed into their tokens, [T, D] float32, in the
    form its shapes give (:func:`_sums_by_rows`)."""
    by_rows = _sums_by_rows(plan.valid.shape[0], plan.on.size)
    return (_row_sums if by_rows else _pair_sums)(rows, gates, plan)


def _no_tangent(plan: HeldRouting):
    return jax.tree_util.tree_map(_zero_tan, plan)


@jax.custom_vjp
def _held_rows(x, plan: HeldRouting):
    """rows[p] = x[row_token[p]]: a gather. Rows past the filled ones hold
    copies nobody reads."""
    return x[plan.row_token]


def _held_rows_fwd(x, plan):
    return x[plan.row_token], plan


def _held_rows_bwd(plan, drows):
    # dx[t] = sum over t's pairs that are held of drows[dest[t, c]]: the
    # mirror of the combine's forward
    dx = _token_sums(drows, None, plan).astype(drows.dtype)
    return dx, _no_tangent(plan)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


@jax.custom_vjp
def _held_sum(out, gates, plan: HeldRouting):
    """y[t] = sum over t's held pairs of gates[t, c] * out[dest[t, c]], in
    float32."""
    return _token_sums(out, gates, plan)


def _held_sum_fwd(out, gates, plan):
    return _held_sum(out, gates, plan), (out, gates, plan)


def _held_sum_bwd(res, dy):
    out, gates, plan = res
    gate_row = jnp.where(plan.valid, gates.reshape(-1)[plan.row_entry], 0)
    if plan.valid.shape[0] < plan.on.size:
        # <out[dest[t, c]], dy[t]> is a number a ROW: the same products in
        # the same order over D from the rows of dy that dout is made of,
        # then T*k scalars
        dy_row = dy[plan.row_token]
        by_row = (out.astype(jnp.float32) * dy_row).sum(-1)
        dgates = jnp.where(plan.on, by_row[plan.dest], 0)
        dy_row = dy_row.astype(out.dtype)
    else:
        # gathered in the rows' own type: a float32 [k*T, D] copy of dy is
        # the layer's largest buffer otherwise
        dy_row = dy.astype(out.dtype)[plan.row_token]
        picked = jnp.where(plan.on[..., None], out[plan.dest], 0)
        dgates = (picked.astype(jnp.float32) * dy[:, None, :]).sum(-1)
    dout = (dy_row * gate_row[:, None]).astype(out.dtype)
    return dout, dgates, _no_tangent(plan)


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def held_dispatch(x, plan: HeldRouting):
    """[T, D] tokens -> [n, D] rows grouped by held expert."""
    return _held_rows(x, plan)


def held_combine(out, gates, plan: HeldRouting):
    """[n, D] expert outputs -> [T, D] float32 gate-weighted sum over each
    token's held pairs (zero for a token with none)."""
    return _held_sum(out, gates, plan)


def grouped_swiglu(rows, w1, w3, w2, plan: HeldRouting):
    """SwiGLU of every held expert on its own rows: three grouped products
    (``jax.lax.ragged_dot``) over ``plan.group_sizes``. ``w1``/``w3``
    [held, D, M], ``w2`` [held, M, D], already in the compute type. What a
    grouped product leaves in the rows past the filled ones is undefined,
    so they are zeroed before anything else reads them."""
    return _expert_products(rows, w1, w3, w2, plan)[-1]


def _grouped_dot(plan: HeldRouting, dtype):
    return _partial(lax.ragged_dot, group_sizes=plan.group_sizes,
                    preferred_element_type=dtype)


def _gated(a, b, filled):
    return jnp.where(filled, jax.nn.silu(a) * b, 0)


def _expert_products(rows, w1, w3, w2, plan: HeldRouting):
    """:func:`grouped_swiglu` and the products it is made of: ``(a = rows
    w1, b = rows w3, out = silu(a) b w2)`` by expert."""
    dot = _grouped_dot(plan, rows.dtype)
    filled = plan.valid[:, None]
    a, b = dot(rows, w1), dot(rows, w3)
    h = _gated(a, b, filled)
    return a, b, jnp.where(filled, dot(h.astype(rows.dtype), w2), 0)


def plan_rows(pairs: int, held: int, routed: int, n_group: int = 1,
              topk_group: int = 1) -> int:
    """Rows of the smaller plan: twice what ``held`` of ``routed`` experts
    get of ``pairs`` (token, choice) pairs in expectation, rounded up to the
    grouped product's row tile, and never more than the whole bound. Under
    group-limited routing the expectation is taken where every token keeps
    the held experts' group, among the ``routed * topk_group / n_group``
    experts of its kept groups: whether a token keeps a group moves with
    the token's neighbours, so the held experts' load swings with the share
    of tokens that keep theirs (Ling-3.0-flash's 8 held of 512 in 8 groups
    of which 4 are kept: up to 2.5 times the plain expectation in one
    layer's step on a v5e, where twice it sent about one step in forty
    through the windows at 6.5 ms more)."""
    tile = 512 if jax.default_backend() == "tpu" else 8
    among = routed * topk_group // n_group
    return min(pairs, -(-2 * pairs * held // (among * tile)) * tile)


def _window(plan: HeldRouting, start, n: int) -> HeldRouting:
    """The plan over rows ``[start, start + n)`` alone: each held expert's
    rows among them, and the pairs whose row lies there. A pair that is not
    ``on`` may point anywhere; what it reads is discarded."""
    ends = jnp.cumsum(plan.group_sizes)
    cut = lambda edge: jnp.clip(edge, start, start + n)
    dest = plan.dest - start
    return HeldRouting(
        *(lax.dynamic_slice_in_dim(rows, start, n)
          for rows in (plan.row_token, plan.row_entry)),
        jnp.clip(dest, 0, n - 1), plan.on & (dest >= 0) & (dest < n),
        lax.dynamic_slice_in_dim(plan.valid, start, n),
        cut(ends) - cut(ends - plan.group_sizes))


def _routed_rows(x, gates, w1, w3, w2, plan: HeldRouting,
                 kept: bool = False):
    """Dispatch, experts and combine over the plan's rows: [T, D] float32;
    with ``kept``, ``(y, (rows, a, b, out))``, the rows and grouped
    products that :func:`_window_grads` reads."""
    with jax.named_scope("hvd_moe_dispatch"):
        rows = held_dispatch(x, plan)
    with jax.named_scope("hvd_moe_experts"):
        a, b, out = _expert_products(rows, w1, w3, w2, plan)
    with jax.named_scope("hvd_moe_combine"):
        y = held_combine(out, gates, plan)
    return (y, (rows, a, b, out)) if kept else y


@_partial(jax.jit, static_argnums=(0, 1))
def _routed_window(n: int, kept: bool, start, plan: HeldRouting, x, gates,
                   w1, w3, w2):
    """:func:`_routed_rows` over rows ``[start, start + n)`` of the plan.
    Jitted so that both branches, forward and backward, of every layer of
    one shape are traced and lowered once."""
    return _routed_rows(x, gates, w1, w3, w2, _window(plan, start, n), kept)


def _transposed(linear, like, ct):
    return jax.linear_transpose(linear, like)(ct)[0]


@_partial(jax.jit, static_argnums=(0,))
def _window_grads(n: int, plan: HeldRouting, kept, dy, gates, w1, w3, w2):
    """The transpose of :func:`_routed_window` over the first ``n`` rows
    from the rows and products its forward kept (``(rows, a, b, out)``):
    the combine's, the six grouped products' own transposes and the
    dispatch's, as differentiation would make them, with no product made
    again. Keeping the rows rather than gathering them again read 0.68%
    more samples a second in the conv cell on a v5e, for 0.43 GB. Returns
    the gradients of ``(x, gates, w1, w3, w2)``."""
    plan = _window(plan, jnp.int32(0), n)
    rows, a, b, out = kept
    with jax.named_scope("hvd_moe_combine"):
        dout, dgates, _ = _held_sum_bwd((out, gates, plan), dy)
    with jax.named_scope("hvd_moe_experts"):
        dot = _grouped_dot(plan, rows.dtype)
        filled = plan.valid[:, None]
        dout = jnp.where(filled, dout, 0)
        h, gated_vjp = jax.vjp(_partial(_gated, filled=filled), a, b)
        dw2 = _transposed(lambda w: dot(h, w), w2, dout)
        da, db = gated_vjp(_transposed(lambda h: dot(h, w2), h, dout))
        dw1 = _transposed(lambda w: dot(rows, w), w1, da)
        dw3 = _transposed(lambda w: dot(rows, w), w3, db)
        drows = _transposed(lambda r: dot(r, w1), rows, da) \
            + _transposed(lambda r: dot(r, w3), rows, db)
    with jax.named_scope("hvd_moe_dispatch"):
        dx, _ = _held_rows_bwd(plan, drows)
    return dx, dgates, dw1, dw3, dw2


def _fits(plan: HeldRouting, small: int):
    """Whether the filled rows fit the first window of ``small``."""
    return plan.group_sizes.sum() <= small


def _every_window(window, like, small: int, plan: HeldRouting, *operands):
    """``window(start, plan, *operands)`` summed in float32 over as many
    windows of ``small`` rows as are filled, so that no array of the
    program has the bound's rows. ``like`` has the result's shapes and
    types."""
    pad = -plan.valid.shape[0] % small      # whole windows
    if pad:
        plan = plan._replace(**{
            name: jnp.pad(getattr(plan, name), (0, pad))
            for name in ("row_token", "row_entry", "valid")})
    total = lax.fori_loop(
        0, (plan.group_sizes.sum() + small - 1) // small,
        lambda w, total: jax.tree_util.tree_map(
            lambda t, part: t + part.astype(t.dtype), total,
            window(w * small, plan, *operands)),
        jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), like))
    return jax.tree_util.tree_map(lambda t, a: t.astype(a.dtype),
                                  total, like)


def _first_window_or_every(window, like, plan: HeldRouting, small: int,
                           *operands):
    """``window(start, plan, *operands)`` over the first ``small`` rows when
    the filled ones fit there, else by :func:`_every_window`."""
    def first(plan, *operands):
        return window(jnp.int32(0), plan, *operands)

    return lax.cond(_fits(plan, small), first,
                    _partial(_every_window, window, like, small),
                    plan, *operands)


@_partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _routed_two_plans(x, gates, w1, w3, w2, plan: HeldRouting, small: int,
                      kept: bool):
    """:func:`_routed_rows` over the smaller plan or over the bound by
    windows (:func:`_first_window_or_every`). The choice lies outside the
    residual path: differentiating through a ``lax.cond`` makes the
    residuals of BOTH branches its outputs. So the forward returns ``y``
    and, with ``kept``, the first window's rows and products named
    :data:`PRODUCTS` (zeros of their shapes from the other branch), and
    saves its inputs; the backward transposes inside the branch it takes,
    the first window from its kept products (:func:`_window_grads`), the
    windows of the other branch by making them again."""
    return _first_window_or_every(
        _partial(_routed_window, small, False),
        jax.ShapeDtypeStruct(x.shape, jnp.float32), plan, small,
        x, gates, w1, w3, w2)


def _routed_two_plans_fwd(x, gates, w1, w3, w2, plan, small, kept):
    args = (x, gates, w1, w3, w2)
    if not kept:
        return _routed_two_plans(*args, plan, small, kept), \
            (*args, plan, None)

    def first(plan, *args):
        return _routed_window(small, True, jnp.int32(0), plan, *args)

    like = jax.eval_shape(first, plan, *args)

    def every(plan, *args):
        return (_every_window(_partial(_routed_window, small, False),
                              like[0], small, plan, *args),
                jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                       like[1]))

    y, products = lax.cond(_fits(plan, small), first, every, plan, *args)
    return y, (*args, plan, checkpoint_name(products, PRODUCTS))


def _routed_two_plans_bwd(small, kept, res, dy):
    *args, plan, products = res

    def pull(start, plan, dy, *args):
        return jax.vjp(_partial(_routed_window, small, False, start, plan),
                       *args)[1](dy)

    if products is None:
        grads = _first_window_or_every(pull, tuple(args), plan, small, dy,
                                       *args)
    else:
        # Two conditionals on the one predicate: an operand of a
        # conditional is live through either branch, so with one the kept
        # products would lie beside the other windows' loop (0.59 GB more
        # of the conv cell's step, by the compiler's account).
        like, fits = tuple(args), _fits(plan, small)
        first = lax.cond(
            fits, lambda plan, products, dy, x, *rest: _window_grads(
                small, plan, products, dy, *rest),
            lambda *_: jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), like),
            plan, products, dy, *args)
        grads = lax.cond(
            fits, lambda first, plan, dy, *args: first,
            lambda first, plan, dy, *args: _every_window(
                pull, like, small, plan, dy, *args),
            first, plan, dy, *args)
    # The barrier keeps XLA from moving the optimizer's float32 copy and
    # square of each weight gradient into both branches, where they become
    # outputs that are all live at once (0.28 GB more of the step's memory
    # at 16 experts of 2048 x 768).
    grads = lax.optimization_barrier(grads)
    return (*grads, _no_tangent(plan))


_routed_two_plans.defvjp(_routed_two_plans_fwd, _routed_two_plans_bwd)


def dropless_share(x, router_logits, bias, w1, w3, w2, *, first: int,
                   top_k: int, scaling: float, keep_products: bool = True,
                   n_group: int = 1, topk_group: int = 1):
    """The routed part of an expert layer that this rank's experts give:
    ``sum over chosen experts i in [first, first + held) of g_i * E_i(x)``.
    No capacity and no dropped pair, whatever the imbalance: the plan's
    bound is every pair, and the rows are worked over a smaller plan
    (:func:`plan_rows`) only in a step whose filled rows fit it.

    The route (the choices, the gates and the plan, which
    :func:`held_plan` places by counting) carries the name :data:`ROUTE`:
    under a rematerialisation that saves it the backward reuses the
    forward's route instead of choosing and placing again, and only what
    the gates' gradient reads of the scores is made again.
    ``keep_products`` says whether the backward gets the small plan's rows
    and grouped products (:data:`PRODUCTS`) from the forward: true where
    nothing rematerialises the layer or the policy saves that name, so that
    no product is made twice; false where a rematerialisation would make
    them again anyway, and the backward makes them itself.

    ``n_group`` and ``topk_group`` limit the choice to a token's best
    groups of experts (:func:`sigmoid_topk_gates`).

    x [T, D]; router_logits [T, E] over ALL experts; bias [E]; w1, w3
    [held, D, M], w2 [held, M, D]. Returns ``(y [T, D] float32, counts [E]
    float32: tokens of this batch that chose each expert, rows [held]
    float32: rows each held expert computed)``."""
    held, routed = w1.shape[0], router_logits.shape[-1]
    pairs = top_k * x.shape[0]
    small = plan_rows(pairs, held, routed, n_group, topk_group)
    _telemetry.inc("hvd_moe_layers_total", held=held, routed=routed,
                   top_k=top_k, plan_rows=small)
    _telemetry.inc("hvd_moe_token_side_total",
                   form="rows" if _sums_by_rows(small, pairs) else "pairs")
    with jax.named_scope("hvd_moe_route"):
        choice, gates = sigmoid_topk_gates(router_logits, bias, top_k,
                                           scaling, n_group, topk_group)
        plan = checkpoint_name(held_plan(choice, first, held), ROUTE)
        counts = jnp.sum(jax.nn.one_hot(
            choice.reshape(-1), routed, dtype=jnp.float32), axis=0)
    if small == pairs:
        y = _routed_rows(x, gates, w1, w3, w2, plan)
    else:
        # trace-time, one a traced layer with a second plan
        _telemetry.inc("hvd_moe_products_total",
                       saved=str(keep_products).lower())
        y = _routed_two_plans(x, gates, w1, w3, w2, plan, small,
                              keep_products)
    return y, counts, plan.group_sizes.astype(jnp.float32)


def balance_bias(bias, counts, rate: float):
    """The selection bias after a step (arXiv:2412.19437 section 2.1.2):
    up by ``rate`` for an expert that got fewer tokens than the mean of the
    GLOBAL batch's ``counts``, down for one that got more."""
    return bias + rate * jnp.sign(counts.mean() - counts)


def share_load(counts, rows, *, first: int, n_group: int = 1,
               topk_group: int = 1):
    """Host-side reading of one layer's router state after a step: the rows
    each held expert received (max and mean over the global batch), the
    share of (token, choice) pairs that landed on held experts, the rows of
    the smaller plan (:func:`plan_rows`, with the router's groups) and
    whether the filled ones fit it, and the pairs dropped, which is 0 by
    construction and asserted."""
    import numpy as np
    counts, rows = np.asarray(counts, np.float64), np.asarray(rows, np.float64)
    held = counts[first:first + len(rows)]
    dropped = int(held.sum() - rows.sum())
    assert dropped == 0, f"{dropped} routed pairs computed no row"
    small = plan_rows(int(counts.sum()), len(rows), len(counts), n_group,
                      topk_group)
    return {"rows_max": float(held.max()), "rows_mean": float(held.mean()),
            "held_share": float(held.sum() / max(counts.sum(), 1.0)),
            "plan_rows": small, "small_plan": bool(rows.sum() <= small),
            "dropped": dropped}
