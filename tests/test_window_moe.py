"""The window/full attention decoder with a gate a head and routed experts
(``models/window_moe.py``) against the plain reference the benchmark holds
(``chipbench/reference/window_moe.py``), on seeded weights at a tiny size:
loss and every gradient (materialised attention, rematerialised layers, the
windowed flash kernels), two data-parallel updates, planted faults that the
comparison must miss each, the head halves and the expert shares adding up
to the uncut layer, YaRN's frequencies and the partial rotation, (scopes and
counters: tests/test_spans.py)."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from chipbench.families.window_moe import MODEL_KEYS
from chipbench.harness import named_leaves
from chipbench.reference import window_moe as reference
from horovod_tpu.models import window_moe
from horovod_tpu.models.latent_moe import ExpertLayer, SwiGLU, next_tokens_loss
from horovod_tpu.models.window_moe import (FULL, SLIDING, GatedAttention,
                                           RopeScheme, WindowMoE,
                                           WindowMoEConfig, rope_partial,
                                           window_moe_tiny, yarn_inv_freq)
from horovod_tpu.optimizer import distributed
from horovod_tpu.parallel import moe
from horovod_tpu.train import create_train_state, make_train_step
from test_latent_moe import _eqns, check_products_kept

OPTIMIZER = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
             "eps": 1e-8, "weight_decay": 0.1}
PUBLISHED_FULL = {
    "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
    "original_max_position_embeddings": 8192, "beta_slow": 1,
    "beta_fast": 32, "attention_factor": 1.4852030263919618,
    "partial_rotary_factor": 0.5}


def _published(scheme):
    """A ``RopeScheme`` as an entry of the published ``rope_parameters``."""
    entry = {"rope_theta": scheme.rope_theta, "rope_type": "default",
             "partial_rotary_factor": scheme.partial_rotary_factor}
    if scheme.factor is not None:
        entry.update(
            rope_type="yarn", factor=scheme.factor,
            original_max_position_embeddings=scheme
            .original_max_position_embeddings, beta_fast=scheme.beta_fast,
            beta_slow=scheme.beta_slow,
            attention_factor=scheme.attention_factor)
    return entry


def reference_config(c):
    """The configuration file's form of a model config, for the reference:
    the counts held here at the top, the published ones beside them."""
    layers = range(c.num_hidden_layers)
    return {**{k: getattr(c, k) for k in MODEL_KEYS},
            "layer_types": list(c.layer_types),
            "mlp_layer_types": list(c.mlp_layer_types),
            "num_key_value_heads": c.kv_heads,
            "num_attention_heads_per_layer": [c.heads(i) for i in layers],
            "num_experts": c.expert_layer.held,
            "rope_parameters": {FULL: _published(c.rope_full),
                                SLIDING: _published(c.rope_sliding)},
            "published": {"num_experts": c.num_experts},
            "deployment": {"first_expert": c.first_expert},
            "optimizer": OPTIMIZER}


def _tokens(seed, batch, seq, vocab=128):
    ids = np.random.RandomState(seed).randint(0, vocab, (batch, seq + 1))
    return jnp.asarray(ids[:, :-1], jnp.int32), \
        jnp.asarray(ids[:, 1:], jnp.int32)


def _flat(params):
    return {k[len("params/"):]: jnp.asarray(v) for k, v in
            named_leaves({"params": params}).items()}


def _init(c, tokens, seed=1):
    """Variables with every norm scale moved off its initial 1, so that
    each is seen by the comparison."""
    variables = nn.unbox(jax.jit(
        lambda r, x: WindowMoE(c).init(r, x, train=False))(
            jax.random.PRNGKey(seed), tokens[:1]))
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return {"params": jax.tree_util.tree_unflatten(tree, leaves),
            "batch_stats": variables["batch_stats"]}


def _system_loss(c, variables, tokens, labels):
    def loss(params):
        out, mutated = WindowMoE(c).apply(
            {**variables, "params": params}, tokens, train=True,
            mutable=["batch_stats"])
        return next_tokens_loss()(out, labels), mutated["batch_stats"]
    return jax.value_and_grad(loss, has_aux=True)(variables["params"])


def _reference_loss(c, variables, tokens, labels):
    ref = reference.build(reference_config(c))
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(ref.loss)(_flat(variables["params"]),
                                            tokens, labels)


def _assert_grads(got, want, atol, rtol):
    got = named_leaves(got)
    assert set(got) == set(want)
    for name, w in want.items():
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(got[name], w, atol=atol, rtol=rtol,
                                   err_msg=name)


@pytest.mark.parametrize("over", [
    dict(),
    dict(remat=True, remat_policy="dots_attn"),
    dict(remat=True, remat_policy="full"),
    dict(layer_types=(SLIDING, FULL, SLIDING),
         mlp_layer_types=("sparse", "dense", "sparse"),
         num_attention_heads_per_layer=(4, 12, 8), sliding_window=3),
    dict(kv_heads_held=2, experts_held=2, first_expert=4),
], ids=["plain", "remat_dots_attn", "remat_full", "another_pattern",
        "a_share"])
def test_loss_and_every_gradient_agree_with_the_reference(over):
    c = window_moe_tiny(**over)
    tokens, labels = _tokens(0, 3, 16)
    variables = _init(c, tokens)
    (loss, stats), grads = _system_loss(c, variables, tokens, labels)
    want, want_grads = _reference_loss(c, variables, tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    _assert_grads(grads, want_grads, atol=5e-6, rtol=2e-4)
    sparse = [f"layer_{i}" for i, kind in enumerate(c.mlp_layer_types)
              if kind == "sparse"]
    assert sorted(stats) == sparse
    for name in sparse:         # ten choices a token in the cell; 3 here
        assert float(stats[name]["moe"]["counts"].sum()) == 3 * 16 * 3
        assert not np.asarray(stats[name]["moe"]["bias"]).any()


def test_the_windowed_flash_path_agrees_with_the_reference():
    """The Pallas kernels in interpret mode, 96 tokens in tiles of ... the
    sequence's own length clamps the blocks, so one tile: the window in the
    one-tile backward; under the policy that saves the kernels' outputs."""
    c = window_moe_tiny(use_flash=True, remat=True, remat_policy="dots_attn",
                        sliding_window=40)
    tokens, labels = _tokens(4, 1, 96)
    variables = _init(c, tokens)
    text = str(jax.make_jaxpr(lambda p: _system_loss(
        c, {**variables, "params": p}, tokens, labels)[0][0])(
            variables["params"]))
    assert "hvd_flash_fwd_window" in text and "hvd_flash_bwd_window" in text
    (loss, _), grads = _system_loss(c, variables, tokens, labels)
    want, want_grads = _reference_loss(c, variables, tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=5e-6)
    _assert_grads(grads, want_grads, atol=1e-5, rtol=1e-3)


def test_two_data_parallel_updates_leave_the_references_state():
    """``create_train_state`` -> ``distributed`` -> ``make_train_step`` over
    8 ranks against two plain steps on the global batch: losses and every
    parameter; the router's counts are of the global batch, ``share_load``
    reads no dropped pair, and the bias stays zero."""
    c = window_moe_tiny(kv_heads_held=2, experts_held=2, first_expert=4,
                        axis_name=hvd.RANK_AXIS)
    tokens, labels = _tokens(3, 16, 16)
    opt = dict(OPTIMIZER)
    dopt = distributed(getattr(optax, opt.pop("name"))(**opt))
    model = WindowMoE(c)
    state = create_train_state(model, jax.random.PRNGKey(7), tokens[:1], dopt)
    flat = _flat(state.params)
    step = make_train_step(model, dopt, next_tokens_loss())
    ref = reference.build(reference_config(c))
    ref_state = ref.init(flat)
    for _ in range(2):
        state, loss = step(state, tokens, labels)
        with jax.default_matmul_precision("highest"):
            flat, ref_state, ref_loss = ref.step(flat, ref_state,
                                                 (tokens, labels))
        np.testing.assert_allclose(loss, ref_loss, rtol=2e-6)
    got = named_leaves({"params": state.params})
    for name, want in flat.items():
        np.testing.assert_allclose(got["params/" + name], want, atol=2e-5,
                                   err_msg=name)
    assert sorted(state.batch_stats) == ["layer_1", "layer_2", "layer_3"]
    for stats in state.batch_stats.values():
        load = moe.share_load(stats["moe"]["counts"], stats["moe"]["rows"],
                              first=4)
        assert load["dropped"] == 0
        assert float(stats["moe"]["counts"].sum()) == 16 * 16 * 3
        assert not np.asarray(stats["moe"]["bias"]).any()


# A wrong program each: what the comparison with the reference must miss.
FAULTS = {
    "window_off": dict(sliding_window=10 ** 6),
    "attention_factor_1": dict(rope_full=dataclasses.replace(
        window_moe_tiny().rope_full, attention_factor=1.0)),
    "whole_head_rotation_in_a_full_layer": dict(
        rope_full=dataclasses.replace(window_moe_tiny().rope_full,
                                      partial_rotary_factor=1.0)),
    "two_choices_for_three": dict(num_experts_per_tok=2),
    "gates_unscaled": dict(moe_routed_scaling_factor=1.0),
    "gate_off": None,
}


def _second_loss(config, variables, tokens, labels):
    """``(first loss, the loss after one AdamW update on the same batch)``,
    as the cell's check reads the system."""
    tx = optax.adamw(1e-2)

    @jax.jit
    def both(params):
        loss = lambda p: _system_loss(config, {**variables, "params": p},
                                      tokens, labels)
        (first, _), grads = loss(params)
        updates, _ = tx.update(grads, tx.init(params), params)
        return first, loss(optax.apply_updates(params, updates))[0][0]
    return [float(x) for x in both(variables["params"])]


@pytest.fixture(scope="module")
def checked():
    """The right program's two losses within 1e-5 of the reference's."""
    c = window_moe_tiny()
    tokens, labels = _tokens(5, 4, 16)
    variables = _init(c, tokens)
    ref = reference.build(reference_config(c))
    flat = _flat(variables["params"])
    tx = optax.adamw(1e-2)
    with jax.default_matmul_precision("highest"):
        first, g = jax.value_and_grad(ref.loss)(flat, tokens, labels)
        up, _ = tx.update(g, tx.init(flat), flat)
        moved = optax.apply_updates(flat, up)  # hvd-analyze: ok
        want = (float(first), float(ref.loss(moved, tokens, labels)))
    right = _second_loss(c, variables, tokens, labels)
    assert max(abs(a - b) / b for a, b in zip(right, want)) < 1e-5
    return c, variables, tokens, labels, want


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_misses_the_reference(fault, checked, monkeypatch):
    """Every wrong program is off the reference by more than a hundred
    times what the right one is, at the first or the second loss."""
    c, variables, tokens, labels, want = checked
    if FAULTS[fault] is None:
        monkeypatch.setattr(window_moe, "head_gate",
                            lambda logits: jnp.ones(logits.shape, jnp.float32))
    else:
        c = dataclasses.replace(c, **FAULTS[fault])
    wrong = _second_loss(c, variables, tokens, labels)
    assert max(abs(a - b) / b for a, b in zip(wrong, want)) > 1e-3, wrong


@pytest.mark.parametrize("layer", [0, 1], ids=["full", "sliding"])
def test_the_head_halves_partial_sums_add_up_to_the_whole_attention(layer):
    """4 KV heads as two halves of 2 with their query heads: each half's
    ``Wo`` product is its part of the layer's sum, and the two add up to the
    whole attention output, the uncut reference's too."""
    whole = window_moe_tiny()
    H, d = whole.heads(layer), whole.head_dim
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.hidden_size))
    positions = jnp.arange(16)[None, :]
    attention = GatedAttention(whole, layer)
    params = nn.unbox(attention.init(jax.random.PRNGKey(5), x,
                                     positions))["params"]
    want = attention.apply({"params": params}, x, positions)

    def half(i):
        """The same weights, cut to KV heads [2i, 2i + 2) and their query
        heads [i H/2, (i + 1) H/2)."""
        q = slice(i * H // 2 * d, (i + 1) * H // 2 * d)
        kv = slice(i * 2 * d, (i + 1) * 2 * d)
        cut = {"wq": {"kernel": params["wq"]["kernel"][:, q]},
               "wk": {"kernel": params["wk"]["kernel"][:, kv]},
               "wv": {"kernel": params["wv"]["kernel"][:, kv]},
               "wg": {"kernel": params["wg"]["kernel"][
                   :, i * H // 2:(i + 1) * H // 2]},
               "wo": {"kernel": params["wo"]["kernel"][q]}}
        return GatedAttention(dataclasses.replace(whole, kv_heads_held=2),
                              layer).apply({"params": cut}, x, positions)

    np.testing.assert_allclose(half(0) + half(1), want, atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(half(0) - want).max()) > 1e-2
    ref = reference.build(reference_config(whole))
    flat = {"attn/" + k[len("params/"):]: jnp.asarray(v)
            for k, v in named_leaves({"params": params}).items()}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref.attention(x[b], flat, "attn/", layer)
                           for b in range(2)])
    np.testing.assert_allclose(want, uncut, atol=1e-5, rtol=1e-5)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2 (the cell: 256 as 32 shares of 8): the
    routed parts that the shares give, with the shared expert counted once,
    are the whole layer's output as the uncut reference computes it."""
    whole = window_moe_tiny()
    layer_config = whole.expert_layer
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.hidden_size))
    variables = nn.unbox(ExpertLayer(layer_config).init(
        jax.random.PRNGKey(5), x))
    params = variables["params"]

    def share(first):
        """The same weights, cut to experts [first, first + 2)."""
        cut = {**params, **{w: params[w][first:first + 2]
                            for w in ("w1", "w3", "w2")}}
        stats = {"bias": jnp.zeros(8), "counts": jnp.zeros(8),
                 "rows": jnp.zeros(2)}
        return ExpertLayer(dataclasses.replace(
            whole, experts_held=2, first_expert=first).expert_layer).apply(
                {"params": cut, "batch_stats": stats}, x)

    shared = SwiGLU(layer_config, whole.shared_expert_intermediate_size
                    ).apply({"params": params["shared"]}, x)
    routed = sum(share(first) - shared for first in (0, 2, 4, 6))
    ref = reference.build(reference_config(whole))
    flat = {"moe/" + k[len("params/"):]: jnp.asarray(v)
            for k, v in named_leaves({"params": params}).items()}
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x.reshape(32, -1), flat, "moe/")
    np.testing.assert_allclose((routed + shared).reshape(32, -1), want,
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(routed).max()) > 1e-2


def test_the_route_is_made_once_under_dots_attn():
    """Two expert layers under the policy that keeps the route: the
    differentiated loss chooses once a layer (one ``top_k``, one sort of
    the k*T pairs, no integer scatter: the pairs' rows are counted), the
    gradients are those of a policy that makes the route again in the
    backward, and the counter says so once a traced layer."""
    from horovod_tpu.core import telemetry as T
    two = dict(layer_types=(FULL, SLIDING, FULL),
               mlp_layer_types=("dense", "sparse", "sparse"),
               num_attention_heads_per_layer=(8, 12, 8))
    tokens, labels = _tokens(5, 3, 16)
    pairs = 3 * 16 * 3
    route = lambda saved: T.active().registry.counter_value(
        "hvd_moe_route_total", placement="count", saved=saved)
    found = {}
    for policy in ("dots_attn", "dots"):
        c = window_moe_tiny(remat=True, remat_policy=policy, **two)
        variables = _init(c, tokens)
        before = {s: route(s) for s in ("true", "false")}
        jaxpr = jax.make_jaxpr(lambda p: _system_loss(
            c, {**variables, "params": p}, tokens, labels))(
                variables["params"]).jaxpr
        found[policy] = (
            {s: route(s) - before[s] for s in before},
            len(_eqns(jaxpr, "top_k")),
            [eqn.invars[0].aval.shape for eqn in _eqns(jaxpr, "sort")],
            len(_eqns(jaxpr, "scatter")),
            _system_loss(c, variables, tokens, labels))
    saved, top_k, sorts, scatters, ((loss, _), grads) = found["dots_attn"]
    assert saved == {"true": 2.0, "false": 0.0}
    assert (top_k, sorts, scatters) == (2, [(pairs,)] * 2, 0)
    made_again, top_k, sorts, _, ((want, _), want_grads) = found["dots"]
    assert made_again == {"true": 0.0, "false": 2.0}
    assert (top_k, sorts) == (4, [(pairs,)] * 4)
    np.testing.assert_allclose(loss, want, rtol=1e-7)
    got, want_grads = named_leaves(grads), named_leaves(want_grads)
    assert set(got) == set(want_grads)
    for name, w in want_grads.items():
        np.testing.assert_allclose(got[name], w, rtol=1e-6,
                                   atol=1e-7 * float(np.abs(w).max()),
                                   err_msg=name)
    router = [n for n in got if n.endswith("router/kernel")]
    assert len(router) == 2 and all(np.abs(got[n]).max() > 0 for n in router)


def test_the_backward_keeps_the_forwards_products_under_dots_attn():
    """Three expert layers, two held of eight experts at three choices:
    144 pairs, a plan of 72 rows."""
    tokens, labels = _tokens(5, 3, 16)
    assert moe.plan_rows(3 * 48, 2, 8) == 72

    def build(policy):
        c = window_moe_tiny(remat=True, remat_policy=policy,
                            experts_held=2, first_expert=4)
        variables = _init(c, tokens)
        model = WindowMoE(c)

        def loss(params):
            out, _ = model.apply({**variables, "params": params}, tokens,
                                 train=True, mutable=["batch_stats"])
            return next_tokens_loss()(out, labels)
        return loss, variables["params"]
    check_products_kept(build, 3)


def test_yarns_32_frequencies_are_the_published_schemes():
    scheme = RopeScheme.from_published(PUBLISHED_FULL)
    assert scheme == WindowMoEConfig().rope_full
    freqs = yarn_inv_freq(scheme, 128)
    assert len(freqs) == 32
    for i, want in {0: 1.0, 9: 2.495541e-2, 10: 1.473477e-2,
                    18: 4.865410e-6, 31: 2.354577e-8}.items():
        assert freqs[i] == pytest.approx(want, rel=1e-6), i
    # below the correction range the plain frequencies, above it the plain
    # ones over the factor, as the reference computes them on its own
    plain = [500000 ** (-2 * i / 64) for i in range(32)]
    assert freqs[:10] == pytest.approx(plain[:10], rel=1e-12)
    assert freqs[18:] == pytest.approx([f / 128 for f in plain[18:]],
                                       rel=1e-12)
    assert freqs == pytest.approx(reference.inv_freq(PUBLISHED_FULL, 128),
                                  rel=1e-12)
    assert scheme.attention_factor == pytest.approx(0.1 * np.log(128) + 1)
    sliding = RopeScheme.from_published(
        {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": 1})
    assert sliding == RopeScheme(10000, 1) == WindowMoEConfig().rope_sliding
    assert yarn_inv_freq(sliding, 128) == pytest.approx(
        [10000 ** (-i / 64) for i in range(64)], rel=1e-12)


def test_a_full_layers_partial_rotation_leaves_depths_64_to_127():
    scheme = WindowMoEConfig().rope_full
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 128))
    positions = jnp.arange(100, 112)[None, :]
    out = rope_partial(x, positions, yarn_inv_freq(scheme, 128),
                       scheme.attention_factor)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    # the rotated half: pairs (i, i + 32), scaled by the attention factor
    norm = lambda t: jnp.sqrt(t[..., :32] ** 2 + t[..., 32:64] ** 2)
    np.testing.assert_allclose(norm(out), scheme.attention_factor * norm(x),
                               rtol=1e-5)
    assert float(jnp.abs(out[..., :64] - x[..., :64]).max()) > 0.1
    # a sliding layer's scheme rotates the whole head
    whole = rope_partial(x, positions,
                         yarn_inv_freq(WindowMoEConfig().rope_sliding, 128))
    assert float(jnp.abs(whole[..., 64:] - x[..., 64:]).max()) > 0.1


def test_the_per_layer_lists_must_agree():
    tokens, _ = _tokens(0, 1, 8)
    with pytest.raises(ValueError, match="lists differ"):
        WindowMoE(window_moe_tiny(mlp_layer_types=("dense",))).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="do not divide"):
        WindowMoE(window_moe_tiny(
            num_attention_heads_per_layer=(8, 12, 12, 6))).init(
                jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="layer_types"):
        WindowMoE(window_moe_tiny(
            layer_types=(FULL, "linear_attention", SLIDING, FULL))).init(
                jax.random.PRNGKey(0), tokens)
