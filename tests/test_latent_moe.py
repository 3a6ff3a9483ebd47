"""The latent-attention / routed-experts model (``models/latent_moe.py``,
``parallel/moe.py dropless_share``) against the plain reference the
benchmark holds (``chipbench/reference/latent_moe.py``), on seeded weights at
a tiny size: logits, loss, gradients, two data-parallel steps of state, the
dropless layer under skew, the shares adding up to the whole layer, and the
selection bias over 2- and 4-rank meshes."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu as hvd
from chipbench.families.latent_moe import MODEL_KEYS
from chipbench.harness import named_leaves
from chipbench.reference import latent_moe as reference
from horovod_tpu.models.latent_moe import (ExpertLayer, LatentMoE, SwiGLU,
                                           latent_moe_tiny, next_tokens_loss)
from horovod_tpu.optimizer import distributed
from horovod_tpu.parallel import moe
from horovod_tpu.train import create_train_state, make_train_step

OPTIMIZER = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
             "eps": 1e-8, "weight_decay": 0.1}


def reference_config(c):
    """The configuration file's form of a model config, for the reference."""
    return {**{k: getattr(c, k) for k in MODEL_KEYS},
            "n_routed_experts": c.held,
            "published": {"n_routed_experts": c.n_routed_experts},
            "deployment": {"first_expert": c.first_expert},
            "assumed": {"bias_update_rate": c.bias_update_rate,
                        "mtp_loss_weight": 0.3},
            "optimizer": OPTIMIZER}


def _tokens(seed, batch, seq, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, seq + 2))
    return jnp.asarray(ids[:, :-1], jnp.int32), \
        jnp.asarray(ids[:, 1:], jnp.int32)


def _init(c, tokens, seed=1):
    variables = jax.jit(lambda r, x: LatentMoE(c).init(r, x, train=False))(
        jax.random.PRNGKey(seed), tokens[:1])
    return variables, {k[len("params/"):]: jnp.asarray(v) for k, v in
                       named_leaves({"params": variables["params"]}).items()}


def _biases(ref, seed=None, n=8):
    """Per-layer selection biases; seeded ones move the choice."""
    rng = np.random.RandomState(seed)
    return {name: jnp.asarray(rng.normal(0, 0.05, n) if seed is not None
                              else np.zeros(n), jnp.float32)
            for name in ref.expert_layers}


def _stats(biases):
    """The reference's ``{layer: bias}`` as the model's state collection."""
    return {name: {"moe": {"bias": b, "counts": jnp.zeros_like(b),
                           "rows": jnp.zeros((2,), jnp.float32)}}
            for name, b in biases.items()}


def test_logits_loss_and_every_gradient_agree_with_the_reference():
    c = latent_moe_tiny()
    tokens, labels = _tokens(0, 3, 16)
    variables, flat = _init(c, tokens)
    ref = reference.build(reference_config(c))
    biases = _biases(ref, seed=5)
    stats = _stats(biases)
    model, loss_fn = LatentMoE(c), next_tokens_loss(0.3)

    trunk, module = model.apply(
        {"params": variables["params"], "batch_stats": stats}, tokens,
        train=False)
    ref_trunk, ref_module = ref.logits(flat, biases, tokens)
    np.testing.assert_allclose(trunk, ref_trunk, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(module, ref_module, atol=2e-5, rtol=2e-5)

    def system_loss(params):
        out = model.apply({"params": params, "batch_stats": stats}, tokens,
                          train=False)
        return loss_fn(out, labels)

    value, grads = jax.value_and_grad(system_loss)(variables["params"])
    (ref_value, _), ref_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        flat, biases, tokens, labels)
    np.testing.assert_allclose(value, ref_value, rtol=1e-6)
    grads = named_leaves(grads)
    assert set(grads) == set(ref_grads)
    for name, want in ref_grads.items():
        np.testing.assert_allclose(grads[name], want, atol=2e-6, rtol=2e-4,
                                   err_msg=name)
    # every parameter is reached, the held experts' and the module's too
    assert all(np.abs(g).max() > 0 for g in grads.values())


def test_two_data_parallel_steps_leave_the_references_state():
    """``create_train_state`` -> ``distributed`` -> ``make_train_step`` over
    8 ranks against two plain steps on the global batch: losses, every
    parameter, every selection bias, and the counts the state carries."""
    c = latent_moe_tiny(axis_name=hvd.RANK_AXIS)
    tokens, labels = _tokens(3, 16, 16)
    opt = dict(OPTIMIZER)
    dopt = distributed(getattr(optax, opt.pop("name"))(**opt))
    model = LatentMoE(c)
    state = create_train_state(model, jax.random.PRNGKey(7), tokens[:1], dopt)
    flat = {k[len("params/"):]: jnp.asarray(v) for k, v in
            named_leaves({"params": state.params}).items()}
    step = make_train_step(model, dopt, next_tokens_loss(0.3))
    ref = reference.build(reference_config(c))
    ref_state = ref.init(flat)
    for _ in range(2):
        state, loss = step(state, tokens, labels)
        flat, ref_state, ref_loss = ref.step(flat, ref_state,
                                             (tokens, labels))
        np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)
    got = named_leaves({"params": state.params})
    for name, want in flat.items():
        np.testing.assert_allclose(got["params/" + name], want, atol=2e-5,
                                   err_msg=name)
    for name, bias in ref_state[1].items():
        mine = state.batch_stats[name]["moe"]
        np.testing.assert_array_equal(mine["bias"], bias)
        assert float(np.abs(bias).max()) == pytest.approx(2e-3)
        load = moe.share_load(mine["counts"], mine["rows"],
                              first=c.first_expert)
        assert load["dropped"] == 0
        assert float(mine["counts"].sum()) == 16 * 16 * 3   # global batch


def _masked_loop(x, logits, bias, w1, w3, w2, first, top_k, scaling):
    """Every token through every held expert, gate 0 unless chosen."""
    s = jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(s + bias, top_k)
    chosen = jax.nn.one_hot(choice, logits.shape[-1]).sum(1)
    gates = scaling * s * chosen / (s * chosen).sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(w1.shape[0]):
        h = jax.nn.silu(x @ w1[j]) * (x @ w3[j])
        y = y + gates[:, first + j, None] * (h @ w2[j])
    return y, chosen.sum(0)


def test_nothing_is_dropped_when_one_held_expert_gets_most_rows():
    T, D, M, E, held, first, k = 96, 16, 8, 8, 2, 4, 3
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (T, D))
    w1, w3 = (jax.random.normal(kk, (held, D, M)) * 0.3 for kk in keys[1:3])
    w2 = jax.random.normal(keys[3], (held, M, D)) * 0.3
    logits = jax.random.normal(keys[4], (T, E))
    bias = jnp.zeros(E).at[first].set(10.0)      # every token picks expert 4

    def share(x, logits, w1, w3, w2):
        y, counts, rows = moe.dropless_share(
            x, logits, bias, w1, w3, w2, first=first, top_k=k, scaling=2.5)
        return (y ** 2).sum(), (y, counts, rows)

    def loop(x, logits, w1, w3, w2):
        y, counts = _masked_loop(x, logits, bias, w1, w3, w2, first, k, 2.5)
        return (y ** 2).sum(), (y, counts)

    args = (x, logits, w1, w3, w2)
    (_, (y, counts, rows)), grads = jax.value_and_grad(
        share, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    (_, (want, want_counts)), want_grads = jax.value_and_grad(
        loop, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    assert float(rows[0]) == T and float(counts[first]) == T
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(rows, counts[first:first + held])
    assert moe.share_load(counts, rows, first=first) == pytest.approx({
        "rows_max": float(T), "rows_mean": float(rows.mean()),
        "held_share": float(rows.sum() / (T * k)), "plan_rows": 144,
        "small_plan": bool(rows.sum() <= 144), "dropped": 0})
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


# the two-plan cases: 1,024 tokens x 4 choices of 16 experts are 4,096 pairs,
# and the smaller plan 2 * 4096 * 2 / 16 = 1,024 rows when 2 experts are held
PLAN_T, PLAN_K, PLAN_E, PLAN_D, PLAN_M, PLAN_FIRST = 1024, 4, 16, 16, 8, 6


def _plan_case(held, filled=None, biased=0, T=PLAN_T, routed=PLAN_E):
    """Inputs of ``dropless_share`` for ``T`` tokens. ``filled`` pins the
    number of held pairs: the held experts' logits are -6 (never among the
    top of 16) but for that many (token, held expert) pairs at +6 (always
    among them). ``biased`` held experts are chosen by every token."""
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (T, PLAN_D))
    w1, w3 = (jax.random.normal(kk, (held, PLAN_D, PLAN_M)) * 0.3
              for kk in keys[1:3])
    w2 = jax.random.normal(keys[3], (held, PLAN_M, PLAN_D)) * 0.3
    logits = jax.random.normal(keys[4], (T, routed))
    if filled is not None:
        mine = np.full(T * held, -6.0, np.float32)
        mine[:filled] = 6.0
        logits = logits.at[:, PLAN_FIRST:PLAN_FIRST + held].set(
            mine.reshape(held, T).T)
    bias = jnp.zeros(routed).at[PLAN_FIRST:PLAN_FIRST + biased].set(10.0)
    return (x, logits, w1, w3, w2), bias


def _share_with_grads(bias, top_k=PLAN_K, kept=True):
    def share(x, logits, w1, w3, w2):
        y, counts, rows = moe.dropless_share(
            x, logits, bias, w1, w3, w2, first=PLAN_FIRST, top_k=top_k,
            scaling=2.5, keep_products=kept)
        return (y ** 2).sum(), (y, counts, rows)
    return jax.value_and_grad(share, argnums=(0, 1, 2, 3, 4), has_aux=True)


def _subjaxprs(jaxpr):
    """``jaxpr`` and every jaxpr nested in its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _subjaxprs(sub)


def _eqns(jaxpr, primitive):
    return [eqn for sub in _subjaxprs(jaxpr) for eqn in sub.eqns
            if eqn.primitive.name == primitive]


def check_products_kept(build, layers):
    """``build(policy)`` -> ``(loss of the parameters, parameters)`` of a
    model with ``layers`` expert layers whose plan is smaller than the
    pairs, rematerialised under ``policy``. Under the policy that keeps the
    small plan's products (``dots_attn``) that branch of a layer's backward
    holds the six products' transposes and no product (6 grouped products,
    not 9; its other branch, the windows made again, lies in a second
    conditional), and no forward conditional runs twice; the gradients are
    those of the policy that makes the products again (``dots``); the
    counter says which once a traced layer."""
    from horovod_tpu.core import telemetry as T
    counted = lambda saved: T.active().registry.counter_value(
        "hvd_moe_products_total", saved=saved)
    found = {}
    for policy in ("dots_attn", "dots"):
        loss, params = build(policy)
        before = {s: counted(s) for s in ("true", "false")}
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
        saved = {s: counted(s) - before[s] for s in before}
        # the first window's branch (index = the predicate) of each
        # conditional, forward and backward
        products = sorted(
            len(_eqns(cond.params["branches"][1].jaxpr,
                      "ragged_dot_general"))
            for cond in _eqns(jaxpr, "cond"))
        found[policy] = saved, products, jax.jit(jax.grad(loss))(params)
    saved, products, grads = found["dots_attn"]
    assert saved == {"true": layers, "false": 0}
    assert products == [0] * layers + [3] * layers + [6] * layers
    saved, products, want = found["dots"]
    assert saved == {"true": 0, "false": layers}
    assert products == [3] * layers + [9] * layers
    got, want = named_leaves(grads), named_leaves(want)
    assert set(got) == set(want)
    for name, w in want.items():
        assert float(np.abs(w).max()) > 0, name
        np.testing.assert_allclose(got[name], w, rtol=1e-6,
                                   atol=1e-7 * float(np.abs(w).max()),
                                   err_msg=name)


def test_the_backward_keeps_the_forwards_products_under_dots_attn():
    """Two trunk expert layers and the module's, two held of eight experts
    at three choices: 144 pairs, a plan of 72 rows."""
    tokens, labels = _tokens(0, 3, 16)
    assert moe.plan_rows(3 * 48, 2, 8) == 72

    def build(policy):
        c = latent_moe_tiny(remat=True, remat_policy=policy)
        variables, _ = _init(c, tokens)
        stats = _stats(_biases(reference.build(reference_config(c)), 5))

        def loss(params):
            out = LatentMoE(c).apply({"params": params, "batch_stats": stats},
                                     tokens, train=False)
            return next_tokens_loss(0.3)(out, labels)
        return loss, variables["params"]
    check_products_kept(build, 3)


@pytest.mark.parametrize("T, k, held, filled, biased, small, fits, routed", [
    pytest.param(1024, 4, 2, None, 0, 1024, True, 16, id="small_plan"),
    pytest.param(1024, 4, 2, None, 1, 1024, False, 16,
                 id="every_token_on_a_held_expert"),
    pytest.param(1024, 4, 2, 1024, 0, 1024, True, 16,
                 id="filled_is_plan_rows"),
    pytest.param(1024, 4, 2, 1025, 0, 1024, False, 16, id="one_row_over"),
    # 200 pairs, all on held experts, in four windows of 56: the last padded
    pytest.param(100, 2, 2, None, 2, 56, False, 16,
                 id="every_window_and_a_ragged_last"),
    pytest.param(1024, 4, 8, None, 0, 4096, True, 16, id="no_second_plan"),
    # of 64 experts: a window of a sixteenth of the pairs, summed row by row
    pytest.param(1024, 4, 2, None, 0, 256, True, 64, id="rows_small_plan"),
    pytest.param(1024, 4, 2, None, 1, 256, False, 64,
                 id="rows_every_window"),
    pytest.param(1024, 4, 2, 257, 0, 256, False, 64,
                 id="rows_one_row_over"),
])
def test_both_plans_give_the_masked_loops_layer_and_drop_nothing(
        T, k, held, filled, biased, small, fits, routed):
    args, bias = _plan_case(held, filled, biased, T, routed)
    assert moe.plan_rows(k * T, held, routed) == small

    def loop(x, logits, w1, w3, w2):
        y, counts = _masked_loop(x, logits, bias, w1, w3, w2, PLAN_FIRST,
                                 k, 2.5)
        return (y ** 2).sum(), (y, counts)

    share = _share_with_grads(bias, k)
    (_, (y, counts, rows)), grads = share(*args)
    (_, (want, want_counts)), want_grads = jax.value_and_grad(
        loop, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    if filled is not None:
        assert float(rows.sum()) == filled
    if biased == held:
        assert float(rows.sum()) == k * T
    assert (float(rows.sum()) <= small) == fits
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(rows,
                                  counts[PLAN_FIRST:PLAN_FIRST + held])
    load = moe.share_load(counts, rows, first=PLAN_FIRST)
    assert load["dropped"] == 0
    assert (load["plan_rows"], load["small_plan"]) == (small, fits)
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    assert float(np.abs(y).max()) > 0
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)
    # one conditional forward and two backward (the first window from the
    # kept products, the other windows made again), or today's program
    assert len(_eqns(jax.make_jaxpr(share)(*args).jaxpr, "cond")) \
        == (3 if small < k * T else 0)


@pytest.mark.parametrize("kept", [True, False],
                         ids=["products_kept", "products_made_again"])
def test_no_branch_holds_an_array_of_the_bounds_rows(kept):
    """Forward and backward, the ``filled <= plan_rows`` branch works on
    ``[1024, .]`` rows with no loop, the other on windows of as many, and
    nothing in either has the bound's 4,096; and the forward conditional
    returns ``y`` and, where they are kept, the first window's rows and
    products (zeros of their shapes from the other branch), so
    differentiation has made no residual of either branch its output."""
    args, bias = _plan_case(2)
    small = moe.plan_rows(PLAN_T * PLAN_K, 2, PLAN_E)
    shapes = lambda cond: [v.aval.shape for v in cond.outvars]
    grads = [args[0].shape, (PLAN_T, PLAN_K), *(a.shape for a in args[2:])]
    conds = _eqns(jax.make_jaxpr(_share_with_grads(bias, kept=kept))(
        *args).jaxpr, "cond")
    # with the products kept, the backward's two branches are two
    # conditionals on the one predicate
    backward = [cond for cond in conds if shapes(cond) == grads]
    forward, = [cond for cond in conds
                if all(cond is not other for other in backward)]
    assert len(backward) == 1 + kept
    assert shapes(forward) == [(PLAN_T, PLAN_D)] + kept * [
        (small, PLAN_D), (small, PLAN_M), (small, PLAN_M), (small, PLAN_D)]
    for direction in ([forward], backward):
        # index = the predicate
        every, first = zip(*(cond.params["branches"] for cond in direction))
        for branches in (every, first):
            rows = {v.aval.shape[0] for branch in branches
                    for sub in _subjaxprs(branch.jaxpr)
                    for eqn in sub.eqns for v in (*eqn.invars, *eqn.outvars)
                    if len(getattr(v.aval, "shape", ())) >= 2}
            assert 1024 in rows and PLAN_T * PLAN_K not in rows
        assert sum(len(_eqns(b.jaxpr, "while")) for b in every) == 1
        assert not any(_eqns(b.jaxpr, "while") for b in first)


def _pair_arrays(jaxpr, k, width):
    """The equations with an operand or result ``[., k, width]`` (the index
    of a gather of ``T * k`` scalars is ``[T, k, 1]``)."""
    return [eqn for sub in _subjaxprs(jaxpr) for eqn in sub.eqns
            if any(getattr(v.aval, "shape", ())[1:] == (k, width)
                   for v in (*eqn.invars, *eqn.outvars))]


@pytest.mark.parametrize("held, routed, small, form", [
    pytest.param(2, 64, 256, "rows", id="a_sixteenth_of_the_pairs"),
    pytest.param(2, 16, 1024, "pairs", id="a_quarter_of_the_pairs"),
    pytest.param(8, 16, 4096, "pairs", id="no_second_plan"),
])
def test_the_token_side_takes_the_form_the_windows_rows_give(
        held, routed, small, form):
    """A window of a sixteenth of the pairs: no equation of either branch,
    forward or backward, has a ``[T, k, D]`` array. A larger one sums pair
    by pair and takes the gate gradient a row; with no second plan the
    program is the one before there were forms: three ``[T, k, D]`` gathers
    (combine, its gate gradient, the dispatch's transpose). The counter says
    which, once a traced layer."""
    from horovod_tpu.core import telemetry as T
    args, bias = _plan_case(held, routed=routed)
    assert moe.plan_rows(PLAN_K * PLAN_T, held, routed) == small
    count = lambda f: T.active().registry.counter_value(
        "hvd_moe_token_side_total", form=f)
    before = {f: count(f) for f in ("rows", "pairs")}
    jaxpr = jax.make_jaxpr(_share_with_grads(bias))(*args).jaxpr
    assert {f: count(f) - before[f] for f in before} == {
        "rows": float(form == "rows"), "pairs": float(form == "pairs")}
    conds = _eqns(jaxpr, "cond")
    if small == PLAN_K * PLAN_T:
        pairs = _pair_arrays(jaxpr, PLAN_K, PLAN_D)
        assert not conds
        assert [e.primitive.name for e in pairs].count("gather") == 3
        return
    assert len(conds) == 3
    working = 0
    for cond in conds:
        for branch in cond.params["branches"]:
            pairs = _pair_arrays(branch.jaxpr, PLAN_K, PLAN_D)
            # the backward's other two branches (zeros, and the first
            # window's gradients passed on) compute nothing
            works = bool(_eqns(branch.jaxpr, "ragged_dot_general"))
            working += works
            assert bool(pairs) == (works and form == "pairs")
    assert working == 4


def _window_case(name):
    """A window of a real :func:`moe.held_plan`, the plan and the window's
    first row."""
    rng = np.random.RandomState(3)

    def some(T, k, routed):
        return np.stack([rng.permutation(routed)[:k] for _ in range(T)])

    if name == "one_filled_row":            # held: experts 4 and 5
        choice = some(16, 2, 4)
        choice[9, 1] = 5
        first, held, start, n = 4, 2, 0, 8
    elif name == "a_token_with_every_held_expert":
        choice = some(32, 4, 16)
        choice[7] = [6, 4, 7, 5]
        first, held, start, n = 4, 4, 0, 40
    elif name == "cut_inside_a_tokens_rows":
        # every token on all three held experts: 3 groups of 8 rows, and
        # rows 5..16 hold the second of three rows of tokens 1..4
        choice = np.tile(np.array([2, 0, 1]), (8, 1))
        first, held, start, n = 0, 3, 5, 12
    elif name == "all_rows_invalid":        # a window past the filled rows
        choice = some(16, 2, 8)
        first, held, start, n = 0, 2, 24, 8
    elif name == "k10_8_held":
        choice = some(64, 10, 32)
        choice[5] = [3, *range(8, 16), 20]          # every held expert
        first, held, start, n = 8, 8, 0, 192
    elif name == "k8_16_held":
        choice = some(64, 8, 32)
        choice[40] = range(24, 32)                  # k held experts
        first, held, start, n = 16, 16, 64, 256
    plan = moe.held_plan(jnp.asarray(choice, jnp.int32), first, held)
    return moe._window(plan, jnp.int32(start), n), plan, start


@pytest.mark.parametrize("name, filled, most", [
    ("one_filled_row", 1, 1), ("a_token_with_every_held_expert", None, 4),
    ("cut_inside_a_tokens_rows", 12, 2), ("all_rows_invalid", 0, 0),
    ("k10_8_held", None, 8), ("k8_16_held", None, 8)])
def test_the_rows_side_gives_the_pair_forms_sums_and_gate_gradient(
        name, filled, most):
    """``_row_sums`` against ``_pair_sums`` (``held_combine``'s form before
    the window had fewer rows than pairs), weighed and plain, and the gate
    gradient a row against the ``[T, k, D]`` one, over windows of plans."""
    win, plan, start = _window_case(name)
    (T, k), n = win.on.shape, win.valid.shape[0]
    if filled is not None:
        assert int(win.valid.sum()) == int(win.on.sum()) == filled
    assert int(win.on.sum(1).max()) == most <= min(k, len(win.group_sizes))
    if name == "cut_inside_a_tokens_rows":      # rows before, in and after
        assert all(int(plan.dest[1, c]) - start in range(*r) for c, r in
                   ((1, (-9, 0)), (2, (0, n)), (0, (n, 99))))
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    out = jax.random.normal(keys[0], (n, 24)).astype(jnp.bfloat16)
    # what lies past the filled rows is undefined, and nothing may read it
    out = jnp.where(win.valid[:, None], out, jnp.nan)
    gates = jax.random.uniform(keys[1], (T, k), minval=0.1)
    dy = jax.random.normal(keys[2], (T, 24))
    for g in (gates, None):
        want = moe._pair_sums(out, g, win)
        got = jax.jit(moe._row_sums)(out, g, win)
        assert got.dtype == want.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   atol=1e-6 * float(np.abs(want).max()))
        assert (float(np.abs(want).max()) > 0) == (most > 0)
    # the gate gradient, a number a row, to the bit; dout as it was
    dout, dgates, _ = moe._held_sum_bwd((out, gates, win), dy)
    picked = jnp.where(win.on[..., None], out[win.dest], 0)
    np.testing.assert_array_equal(
        dgates, (picked.astype(jnp.float32) * dy[:, None, :]).sum(-1))
    gate_row = jnp.where(win.valid, gates.reshape(-1)[win.row_entry], 0)
    np.testing.assert_array_equal(
        dout, (dy.astype(out.dtype)[win.row_token]
               * gate_row[:, None]).astype(out.dtype))


def _sorted_plan(choice, first, held):
    """The plan as a sort makes all of it: the stable sort of the pairs by
    expert, its permutation turned round by a scatter for each pair's row,
    the experts' rows by a one-hot sum."""
    T, k = choice.shape
    local = choice - first
    on = (local >= 0) & (local < held)
    key = jnp.where(on, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    group_sizes = jnp.sum(
        jax.nn.one_hot(key, held + 1, dtype=jnp.int32), axis=0)[:held]
    rows = jnp.arange(k * T, dtype=jnp.int32)
    dest = jnp.zeros_like(order).at[order].set(rows).reshape(T, k)
    return moe.HeldRouting(order // k, order, dest, on,
                           rows < group_sizes.sum(), group_sizes)


def _gathered_gates(logits, bias, top_k, scaling):
    """``sigmoid_topk_gates`` with the chosen scores gathered."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, choice = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    return choice, scaling * picked / picked.sum(-1, keepdims=True)


@pytest.mark.parametrize("T, k, held, routed, case", [
    pytest.param(512, 10, 8, 256, "router", id="8_of_256_at_k10"),
    pytest.param(512, 8, 16, 256, "router", id="16_of_256_at_k8"),
    pytest.param(256, 4, 8, 64, "one_expert", id="every_pair_on_one_expert"),
    pytest.param(256, 4, 8, 64, "none", id="no_pair_held"),
    pytest.param(512, 10, 8, 256, "crowded", id="filled_past_plan_rows"),
])
def test_the_counted_plan_is_the_sorted_plan(T, k, held, routed, case):
    """Every field of :func:`moe.held_plan` equals the sorted plan's, bit
    for bit and type for type; from a router's choices the choice, the gates
    and the gates' gradient to the logits equal those of gathered scores."""
    first = 8
    logits = jax.random.normal(jax.random.PRNGKey(T + k + held), (T, routed))
    bias = jnp.zeros(routed)
    if case == "crowded":       # every token on the held experts: windows
        bias = bias.at[first:first + held].set(10.0)
    choice, gates = jax.jit(moe.sigmoid_topk_gates, static_argnums=(2, 3))(
        logits, bias, k, 2.5)
    if case == "one_expert":
        choice = jnp.full((T, k), first + 3, jnp.int32)
    elif case == "none":
        choice = jnp.asarray(np.random.RandomState(0).randint(
            first + held, routed, (T, k)), jnp.int32)
    else:
        want_choice, want_gates = jax.jit(
            _gathered_gates, static_argnums=(2, 3))(logits, bias, k, 2.5)
        np.testing.assert_array_equal(choice, want_choice)
        np.testing.assert_array_equal(gates, want_gates)

        def pull(gates_of):
            return jax.grad(lambda lg: (gates_of(lg, bias, k, 2.5)[1]
                                        * jnp.arange(k)).sum())(logits)
        np.testing.assert_array_equal(pull(moe.sigmoid_topk_gates),
                                      pull(_gathered_gates))
    got = jax.jit(moe.held_plan, static_argnums=(1, 2))(choice, first, held)
    want = _sorted_plan(choice, first, held)
    for name in moe.HeldRouting._fields:
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    filled = int(got.group_sizes.sum())
    assert filled == {"one_expert": k * T, "none": 0}.get(case, filled)
    if case == "crowded":
        assert filled > moe.plan_rows(k * T, held, routed)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2: the routed parts that the shares give,
    with the shared expert counted once, are the whole layer's output as
    the uncut reference computes it."""
    whole = latent_moe_tiny(experts_held=None, first_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.hidden_size))
    layer = ExpertLayer(whole)
    variables = layer.init(jax.random.PRNGKey(5), x)
    params = nn.meta.unbox(variables["params"])
    bias = jnp.asarray(np.random.RandomState(6).normal(0, 0.05, 8),
                       jnp.float32)

    def share(first):
        """The same weights, cut to experts [first, first + 2)."""
        cut = {**params, **{w: params[w][first:first + 2]
                            for w in ("w1", "w3", "w2")}}
        stats = {"bias": bias, "counts": jnp.zeros(8), "rows": jnp.zeros(2)}
        return ExpertLayer(dataclasses.replace(
            whole, experts_held=2, first_expert=first)).apply(
                {"params": cut, "batch_stats": stats}, x)

    shared = SwiGLU(whole, whole.moe_intermediate_size).apply(
        {"params": params["shared"]}, x)
    routed = sum(share(first) - shared for first in (0, 2, 4, 6))
    ref = reference.build(reference_config(whole))
    flat = {"moe/" + k[len("params/"):]: jnp.asarray(v)
            for k, v in named_leaves({"params": params}).items()}
    want, counts = ref.experts(x.reshape(32, -1), flat, "moe/", bias)
    np.testing.assert_allclose((routed + shared).reshape(32, -1), want,
                               atol=1e-5, rtol=1e-5)
    assert float(counts.sum()) == 32 * 3


@pytest.mark.parametrize("ranks", [2, 4])
def test_the_bias_over_ranks_is_the_single_process_bias(ranks):
    """Counts are summed over the rank axis inside the model before the
    sign is taken: ``b`` after one step on ``ranks`` devices equals ``b``
    after one step of one process on the global batch."""
    tokens, labels = _tokens(6, 8, 16)
    opt = dict(OPTIMIZER)
    tx = getattr(optax, opt.pop("name"))(**opt)

    def bias_after_one_step(devices, axis):
        mesh = Mesh(np.array(devices), (axis,))
        c = latent_moe_tiny(axis_name=axis)
        dopt = distributed(tx, axis_name=axis)
        model = LatentMoE(c)
        state = create_train_state(model, jax.random.PRNGKey(9), tokens[:1],
                                   dopt, mesh=mesh)
        step = make_train_step(model, dopt, next_tokens_loss(0.3),
                               mesh=mesh, axis_name=axis)
        state, _ = step(state, tokens, labels)
        return {name: (np.asarray(s["moe"]["bias"]),
                       np.asarray(s["moe"]["counts"]))
                for name, s in state.batch_stats.items()}

    many = bias_after_one_step(jax.devices()[:ranks], "r")
    one = bias_after_one_step(jax.devices()[:1], "r")
    assert set(many) == {"layer_1", "layer_2", "mtp_block"}
    for name in one:
        np.testing.assert_array_equal(many[name][1], one[name][1])
        np.testing.assert_array_equal(many[name][0], one[name][0])
        assert np.abs(one[name][0]).max() > 0


def test_the_layers_carry_their_scopes_and_the_counter_fires_once_a_trace():
    from horovod_tpu.core import telemetry as T
    c = latent_moe_tiny(axis_name=hvd.RANK_AXIS)
    tokens, labels = _tokens(8, 8, 16)
    dopt = distributed(optax.sgd(0.1))
    model = LatentMoE(c)
    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1], dopt)
    labelled = dict(held=2, routed=8, top_k=3, plan_rows=24)
    before = T.active().registry.counter_value("hvd_moe_layers_total",
                                               **labelled)
    step = make_train_step(model, dopt, next_tokens_loss(0.3))
    text = step.lower(state, tokens, labels).as_text(debug_info=True)
    for scope in ("hvd_mla", "hvd_moe_route", "hvd_moe_dispatch",
                  "hvd_moe_experts", "hvd_moe_shared", "hvd_moe_combine",
                  "hvd_mtp"):
        # the routed part's gathers lie in functions that every layer
        # shares, and start their names at the scope
        assert f"hvd_forward/LatentMoE/{scope}" in text \
            or f"/{scope}" in text or f'"{scope}/' in text, scope
    # trace-time: one a traced expert layer (two in the trunk, the module's)
    assert T.active().registry.counter_value(
        "hvd_moe_layers_total", **labelled) - before == 3.0
    # the two plans' conditionals hold no collective and carry no state
    from horovod_tpu.analysis import analyze_step
    assert analyze_step(step, state, tokens, labels) == []
