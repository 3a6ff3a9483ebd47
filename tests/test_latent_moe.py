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
        "held_share": float(rows.sum() / (T * k)), "dropped": 0})
    np.testing.assert_allclose(y, want, atol=1e-5, rtol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


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
    labelled = dict(held=2, routed=8, top_k=3)
    before = T.active().registry.counter_value("hvd_moe_layers_total",
                                               **labelled)
    step = make_train_step(model, dopt, next_tokens_loss(0.3))
    text = step.lower(state, tokens, labels).as_text(debug_info=True)
    for scope in ("hvd_mla", "hvd_moe_route", "hvd_moe_dispatch",
                  "hvd_moe_experts", "hvd_moe_shared", "hvd_moe_combine",
                  "hvd_mtp"):
        assert f"hvd_forward/LatentMoE/{scope}" in text \
            or f"/{scope}" in text, scope
    # trace-time: one a traced expert layer (two in the trunk, the module's)
    assert T.active().registry.counter_value(
        "hvd_moe_layers_total", **labelled) - before == 3.0
