"""The looped decoder (``models/looped.py``) against the plain reference the
benchmark holds (``chipbench/reference/looped.py``), on seeded weights at a
tiny size: loss and every gradient (materialised attention, rematerialised
layers, the flash path), the tied gradient as the sum over untied copies of
the stack, the exit distribution by hand, the head loss against the plain
one, two data-parallel steps with the exit share the state carries, and the
scopes, the counter and the gauges."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chipbench.families.looped import MODEL_KEYS
from chipbench.harness import named_leaves
from chipbench.reference import looped as reference
from horovod_tpu.models import looped
from horovod_tpu.models.llama import RMSNorm
from horovod_tpu.models.looped import (Looped, LoopedConfig, SandwichBlock,
                                       exit_log_distribution, looped_loss,
                                       looped_tiny, pass_cross_entropies)
from horovod_tpu.optimizer import distributed
from horovod_tpu.train import create_train_state, make_train_step

OPTIMIZER = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
             "eps": 1e-8, "weight_decay": 0.1}
BETA = 0.1


def reference_config(c, beta=BETA):
    """The configuration file's form of a model config, for the reference."""
    return {**{k: getattr(c, k) for k in MODEL_KEYS},
            "assumed": {"exit_entropy_weight": beta}, "optimizer": OPTIMIZER}


def _tokens(seed, batch, seq, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, seq + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), \
        jnp.asarray(ids[:, 1:], jnp.int32)


def _flat(params):
    return {k[len("params/"):]: jnp.asarray(v) for k, v in
            named_leaves({"params": params}).items()}


def _init(c, tokens, seed=1):
    """Variables with every norm scale and the gate's bias moved off their
    initial 1 and 0, so that each is seen by the comparison."""
    variables = jax.jit(lambda r, x: Looped(c).init(r, x, train=False))(
        jax.random.PRNGKey(seed), tokens[:1])
    variables = nn.unbox(variables)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return {"params": jax.tree_util.tree_unflatten(tree, leaves),
            "batch_stats": variables["batch_stats"]}


def _system_loss(c, variables, tokens, labels, beta=BETA):
    def loss(params):
        out, mutated = Looped(c).apply(
            {**variables, "params": params}, tokens, train=True,
            mutable=["batch_stats"])
        return looped_loss(beta)(out, labels), mutated["batch_stats"]
    return jax.value_and_grad(loss, has_aux=True)(variables["params"])


def _reference_loss(c, variables, tokens, labels, beta=BETA):
    ref = reference.build(reference_config(c, beta))
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(ref.loss)(_flat(variables["params"]),
                                            tokens, labels)


def _assert_grads(got, want, atol, rtol):
    got = named_leaves(got)
    assert set(got) == set(want)
    for name, w in want.items():
        assert float(jnp.abs(w).max()) > 0 or "exit_gate" in name, name
        np.testing.assert_allclose(got[name], w, atol=atol, rtol=rtol,
                                   err_msg=name)


@pytest.mark.parametrize("over", [
    dict(),
    dict(remat=True, remat_policy="attn"),
    dict(remat=True, remat_policy="full"),
    dict(total_ut_steps=4, num_hidden_layers=1),
], ids=["plain", "remat_attn", "remat_full", "four_passes_one_layer"])
def test_loss_and_every_gradient_agree_with_the_reference(over):
    c = looped_tiny(**over)
    tokens, labels = _tokens(0, 3, 16)
    variables = _init(c, tokens)
    (loss, stats), grads = _system_loss(c, variables, tokens, labels)
    want, want_grads = _reference_loss(c, variables, tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    _assert_grads(grads, want_grads, atol=2e-6, rtol=2e-4)
    assert stats["exit_share"].shape == (c.total_ut_steps,)


def test_the_flash_path_at_512_tokens_agrees_with_the_reference():
    """The Pallas kernels in interpret mode, one tile of 512, under the
    policy that saves their outputs."""
    c = looped_tiny(use_flash=True, remat=True, remat_policy="attn",
                    num_attention_heads=2, num_key_value_heads=2)
    tokens, labels = _tokens(4, 1, 512)
    variables = _init(c, tokens)
    text = jax.jit(lambda p: _system_loss(
        c, {**variables, "params": p}, tokens, labels)[0][0]).lower(
            variables["params"]).as_text()
    assert "hvd_flash" in text or "_fa_" in text
    (loss, _), grads = _system_loss(c, variables, tokens, labels)
    want, want_grads = _reference_loss(c, variables, tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=5e-6)
    _assert_grads(grads, want_grads, atol=1e-5, rtol=1e-3)


def test_the_tied_gradient_is_the_sum_over_untied_copies_of_the_stack():
    """T copies of the stack's parameters, pass t through copy t: at equal
    copies the loss is the loop's, and the gradients of the copies add up
    to the gradient of the shared stack."""
    c = looped_tiny()
    T, L = c.total_ut_steps, c.num_hidden_layers
    tokens, labels = _tokens(1, 2, 12)
    variables = _init(c, tokens)
    params = variables["params"]
    stack_keys = [f"layer_{i}" for i in range(L)] + ["final_norm"]
    shared = {k: v for k, v in params.items() if k not in stack_keys}

    def untied(copies):
        positions = jnp.arange(tokens.shape[1])[None, :]
        x = jnp.take(shared["embedding"], tokens, axis=0)
        hidden, gates = [], []
        for copy in copies:
            for i in range(L):
                x = SandwichBlock(c.layer).apply(
                    {"params": copy[f"layer_{i}"]}, x, positions)
            x = RMSNorm(c.rms_norm_eps, c.dtype).apply(
                {"params": copy["final_norm"]}, x)
            hidden.append(x)
            gates.append((x @ shared["exit_gate"]["kernel"])[..., 0]
                         + shared["exit_gate"]["bias"][0])
        out = looped.LoopedOutput(jnp.stack(hidden), jnp.stack(gates),
                                  shared["lm_head"])
        return looped_loss(BETA)(out, labels)

    copies = [{k: params[k] for k in stack_keys} for _ in range(T)]
    loss, grads = jax.value_and_grad(untied)(copies)
    (tied_loss, _), tied = _system_loss(c, variables, tokens, labels)
    np.testing.assert_allclose(loss, tied_loss, rtol=1e-6)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *grads)
    for name, want in named_leaves(summed).items():
        got = named_leaves(tied)[name]
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-4,
                                   err_msg=name)
    # and no single copy's gradient is the whole of it
    first = named_leaves(grads[0])["layer_0/mlp/w2/kernel"]
    assert np.abs(first - named_leaves(tied)["layer_0/mlp/w2/kernel"]).max() \
        > 1e-3 * np.abs(first).max()


def _count(c):
    shapes = jax.eval_shape(
        lambda r, x: Looped(c).init(r, x, train=False),
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes["params"]))


@pytest.mark.parametrize("c,want", [
    (looped_tiny(), 2 * 128 * 64 + 2 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64)
     + 64 + 64 + 1),
    (LoopedConfig(num_hidden_layers=8), 612_438_017),
], ids=["tiny", "published_widths_8_layers"])
def test_the_parameter_count_does_not_depend_on_the_passes(c, want):
    assert _count(c) == want
    for passes in (1, 2, 5):
        assert _count(dataclasses.replace(c, total_ut_steps=passes)) == want


def test_one_pass_is_plain_next_token_cross_entropy_with_zero_entropy():
    c = looped_tiny(total_ut_steps=1)
    tokens, labels = _tokens(2, 2, 16)
    variables = _init(c, tokens)
    out = Looped(c).apply(variables, tokens, train=False)
    assert out.hidden.shape == (1, 2, 16, 64) and out.gate.shape == (1, 2, 16)
    logits = out.logits()
    plain = optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()
    for beta in (0.0, 0.1, 5.0):        # the entropy of one outcome is 0
        np.testing.assert_allclose(looped_loss(beta)(out, labels), plain,
                                   rtol=1e-6)
    np.testing.assert_array_equal(exit_log_distribution(out.gate), 0.0)


def test_the_exit_distribution_matches_a_hand_computed_four_pass_case():
    lam = np.array([0.5, 0.25, 0.8, 0.3])       # the last gate is not used
    gate = jnp.asarray(np.log(lam / (1 - lam)), jnp.float32)[:, None]
    want = np.array([0.5, 0.5 * 0.25, 0.5 * 0.75 * 0.8, 0.5 * 0.75 * 0.2])
    p = np.exp(exit_log_distribution(gate))[:, 0]
    np.testing.assert_allclose(p, want, rtol=1e-6)
    assert want.sum() == pytest.approx(1.0)
    # the loss on it: sum_t p_t CE_t - beta H(p), CE by hand
    hidden = jnp.eye(4, dtype=jnp.float32)[:, None, None, :]    # [T,1,1,4]
    head = jnp.asarray(np.log([[1, 2, 3, 4], [4, 3, 2, 1], [1, 1, 1, 1],
                               [8, 1, 1, 1]]), jnp.float32)
    out = looped.LoopedOutput(hidden, gate[:, :, None], head)
    ce = -np.log(np.array([3 / 10, 2 / 10, 1 / 4, 1 / 11]))     # label 2
    entropy = -(want * np.log(want)).sum()
    np.testing.assert_allclose(
        looped_loss(0.1)(out, jnp.full((1, 1), 2)),
        (want * ce).sum() - 0.1 * entropy, rtol=1e-6)


def test_the_exit_distribution_sums_to_one_and_survives_saturated_gates():
    gate = jax.random.normal(jax.random.PRNGKey(3), (4, 5, 7)) * 3
    gate = gate.at[:, 0, 0].set(jnp.array([200.0, -200.0, 200.0, 0.0]))
    gate = gate.at[:, 0, 1].set(-200.0)
    logp = exit_log_distribution(gate)
    np.testing.assert_allclose(np.exp(logp).sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.exp(logp[:, 0, 0]), [1, 0, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.exp(logp[:, 0, 1]), [0, 0, 0, 1], atol=1e-6)

    def loss(gate):
        out = looped.LoopedOutput(jnp.zeros((4, 5, 7, 2)), gate,
                                  jnp.ones((2, 3)))
        return looped_loss(0.1)(out, jnp.zeros((5, 7), jnp.int32))
    value, grad = jax.value_and_grad(loss)(gate)
    assert np.isfinite(value) and np.isfinite(grad).all()


def test_the_rematerialised_head_loss_equals_the_plain_one():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    hidden = jax.random.normal(keys[0], (3, 2, 8, 16))
    head = jax.random.normal(keys[1], (16, 40)) * 0.3
    labels = jax.random.randint(keys[2], (2, 8), 0, 40)

    def plain(hidden, head):
        z = jnp.einsum("tbsd,dv->tbsv", hidden, head,
                       precision=jax.lax.Precision.HIGHEST)
        return optax.softmax_cross_entropy_with_integer_labels(
            z, jnp.broadcast_to(labels, z.shape[:-1]))

    weigh = jnp.arange(1.0, 3 * 2 * 8 + 1).reshape(3, 2, 8)
    got, got_grads = jax.value_and_grad(
        lambda h, w: (weigh * pass_cross_entropies(h, w, labels)).sum(),
        argnums=(0, 1))(hidden, head)
    want, want_grads = jax.value_and_grad(
        lambda h, w: (weigh * plain(h, w)).sum(), argnums=(0, 1))(hidden, head)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-5)
    # no [T, ..., V] array in the program: one pass's logits at a time
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w: pass_cross_entropies(h, w, labels).sum(),
        argnums=(0, 1)))(hidden, head)
    assert "f32[3,2,8,40]" not in str(jaxpr) and "f32[2,8,40]" in str(jaxpr)


def test_a_passes_logits_are_the_references():
    c = looped_tiny()
    tokens, _ = _tokens(6, 2, 16)
    variables = _init(c, tokens)
    out = Looped(c).apply(variables, tokens, train=False)
    ref = reference.build(reference_config(c))
    flat = _flat(variables["params"])
    with jax.default_matmul_precision("highest"):
        states = ref.hidden(flat, tokens)
    for t, h in enumerate(states):
        np.testing.assert_allclose(out.logits(t), h @ flat["lm_head"],
                                   atol=2e-5, rtol=1e-4)


def test_two_data_parallel_steps_leave_the_references_state_and_exit_share():
    """``create_train_state`` -> ``distributed`` -> ``make_train_step`` over
    8 ranks against two plain steps on the global batch: losses, every
    parameter, and the exit share the state carries, which is the mean of
    the reference's exit distribution over the GLOBAL batch."""
    c = looped_tiny()
    tokens, labels = _tokens(3, 16, 16)
    opt = dict(OPTIMIZER)
    dopt = distributed(getattr(optax, opt.pop("name"))(**opt))
    model = Looped(c)
    state = create_train_state(model, jax.random.PRNGKey(7), tokens[:1], dopt)
    assert np.asarray(state.batch_stats["exit_share"]).tolist() == [0, 0, 0]
    flat = _flat(state.params)
    step = make_train_step(model, dopt, looped_loss(BETA))
    ref = reference.build(reference_config(c))
    ref_state = ref.init(flat)
    for _ in range(2):
        with jax.default_matmul_precision("highest"):
            shares = ref.exit_distribution(flat, ref.hidden(flat, tokens))
        state, loss = step(state, tokens, labels)
        flat, ref_state, ref_loss = ref.step(flat, ref_state,
                                             (tokens, labels))
        np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)
        np.testing.assert_allclose(state.batch_stats["exit_share"],
                                   [float(s.mean()) for s in shares],
                                   rtol=1e-5)
    assert float(state.batch_stats["exit_share"].sum()) == pytest.approx(1.0)
    got = named_leaves({"params": state.params})
    for name, want in flat.items():
        np.testing.assert_allclose(got["params/" + name], want, atol=2e-5,
                                   err_msg=name)


def test_bfloat16_compute_keeps_float32_gate_logits_and_loss():
    c = looped_tiny(dtype=jnp.bfloat16, remat=True, remat_policy="attn")
    tokens, labels = _tokens(8, 2, 16)
    variables = _init(c, tokens)
    out = Looped(c).apply(variables, tokens, train=False)
    assert out.hidden.dtype == jnp.bfloat16
    assert out.gate.dtype == out.head.dtype == out.logits(0).dtype \
        == jnp.float32
    (loss, _), grads = _system_loss(c, variables, tokens, labels)
    want, _ = _reference_loss(c, variables, tokens, labels)
    assert loss.dtype == jnp.float32
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))
    np.testing.assert_allclose(loss, want, rtol=5e-3)


def test_the_step_carries_its_scopes_and_the_counter_fires_once_a_trace():
    from horovod_tpu.core import telemetry as T
    c = looped_tiny()
    tokens, labels = _tokens(9, 8, 16)
    dopt = distributed(optax.sgd(0.1))
    model = Looped(c)
    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1], dopt)
    labelled = dict(layers=2, passes=3)
    before = T.active().registry.counter_value("hvd_loop_models_total",
                                               **labelled)
    step = make_train_step(model, dopt, looped_loss(BETA))
    text = step.lower(state, tokens, labels).as_text(debug_info=True)
    for scope in ("hvd_loop_pass", "hvd_loop_head", "hvd_exit_gate"):
        assert f"/{scope}/" in text, scope
    assert "hvd_forward)/Looped/hvd_loop_pass/layer_1/" in text
    assert T.active().registry.counter_value(
        "hvd_loop_models_total", **labelled) - before == 1.0
    from horovod_tpu.analysis import analyze_step
    assert analyze_step(step, state, tokens, labels) == []


def test_exit_gauges_read_the_share_the_state_carries():
    from horovod_tpu.core import telemetry as T
    share = jnp.asarray([0.5, 0.125, 0.3, 0.075], jnp.float32)
    got = looped.exit_gauges(share)
    registry = T.active().registry
    for t, want in enumerate([0.5, 0.125, 0.3, 0.075], start=1):
        assert registry.gauge_value("hvd_loop_exit_share", **{"pass": t}) \
            == pytest.approx(want)
    mean_pass = 0.5 + 2 * 0.125 + 3 * 0.3 + 4 * 0.075
    assert registry.gauge_value("hvd_loop_exit_mean_pass") \
        == pytest.approx(mean_pass)
    assert got["exit_mean_pass"] == pytest.approx(mean_pass)
    assert got["exit_share"] == pytest.approx([0.5, 0.125, 0.3, 0.075])
