"""The gated short-convolution / attention decoder with routed experts
(``models/conv_moe.py``) against its equations and against the plain
reference the benchmark holds (``chipbench/reference/conv_moe.py``), on
seeded weights at a tiny size: the taps are causal and are the equations
(and ``transformers``' ``Lfm2ShortConv`` where it is installed), loss and
every gradient, the selection bias's step, planted faults that the
comparison must see, the expert shares adding up to the uncut layer; the two shared-code changes it needed leave the other
models as they were (an expert layer with no shared expert, a per-head q/k
norm that is off by default); the scopes and the counter in a traced
data-parallel step."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.linen import partitioning as nn_partitioning

import horovod_tpu as hvd
from chipbench.families.conv_moe import MODEL_KEYS
from chipbench.harness import named_leaves
from chipbench.reference import conv_moe as reference
from horovod_tpu.core import telemetry as T
from horovod_tpu.models import llama, looped
from horovod_tpu.models.conv_moe import (CONV, FULL, ConvMoE, ConvMoEConfig,
                                         ShortConv, conv_moe_tiny,
                                         gated_short_conv)
from horovod_tpu.models.latent_moe import (ExpertLayer, LatentMoE, SwiGLU,
                                           latent_moe_tiny, next_tokens_loss)
from horovod_tpu.models.window_moe import WindowMoE, window_moe_tiny
from horovod_tpu.optimizer import distributed
from horovod_tpu.train import create_train_state, make_train_step

OPTIMIZER = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
             "eps": 1e-8, "weight_decay": 0.1}


def reference_config(c):
    """The configuration file's form of a model config, for the reference:
    the counts held here at the top, the published ones beside them."""
    return {**{k: getattr(c, k) for k in MODEL_KEYS},
            "layer_types": list(c.layer_types),
            "num_experts": c.expert_layer.held,
            "published": {"num_experts": c.num_experts},
            "deployment": {"first_expert": c.first_expert},
            "assumed": {"bias_update_rate": c.bias_update_rate},
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
        lambda r, x: ConvMoE(c).init(r, x, train=False))(
            jax.random.PRNGKey(seed), tokens[:1]))
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    leaves = [x + 0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return {"params": jax.tree_util.tree_unflatten(tree, leaves),
            "batch_stats": variables["batch_stats"]}


# --- the taps ----------------------------------------------------------------

def _bcu(seed, shape=(2, 12, 3 * 8)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def test_the_taps_are_causal():
    """Changing positions after t leaves every output up to t as it was,
    to the bit, in the mixer alone and in the whole model."""
    bcu, taps = _bcu(0), jax.random.normal(jax.random.PRNGKey(1), (3, 8))
    z = gated_short_conv(bcu, taps)
    for t in (0, 1, 5, 10):
        later = bcu.at[:, t + 1:].set(_bcu(t + 2)[:, t + 1:])
        changed = gated_short_conv(later, taps)
        np.testing.assert_array_equal(changed[:, :t + 1], z[:, :t + 1])
        assert not np.array_equal(changed[:, t + 1:], z[:, t + 1:])
    c = conv_moe_tiny()
    tokens, _ = _tokens(0, 2, 16)
    variables = _init(c, tokens)
    apply = jax.jit(lambda x: ConvMoE(c).apply(variables, x, train=False))
    logits = apply(tokens)
    for t in (3, 9):
        changed = apply(tokens.at[:, t + 1:].set((tokens[:, t + 1:] + 7)
                                                 % 128))
        np.testing.assert_array_equal(changed[:, :t + 1], logits[:, :t + 1])


def _equations(bcu, taps):
    """B, C, u in that order along the features; w_0 on v_{t-2}, w_1 on
    v_{t-1}, w_2 on v_t; zeros before position 0. Loops over numpy."""
    bcu, taps = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    D = taps.shape[1]
    b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    v = b * u
    z = np.zeros_like(v)
    for t in range(v.shape[-2]):
        for j, back in enumerate((2, 1, 0)):
            if t - back >= 0:
                z[..., t, :] += taps[j] * v[..., t - back, :]
    return c * z


def test_the_mixer_is_the_equations():
    bcu, taps = _bcu(3), jax.random.normal(jax.random.PRNGKey(4), (3, 8))
    np.testing.assert_allclose(gated_short_conv(bcu, taps),
                               _equations(bcu, taps), rtol=1e-5, atol=1e-6)
    # B and C swapped, or the taps turned round, are another function
    swapped = jnp.concatenate([bcu[..., 8:16], bcu[..., :8], bcu[..., 16:]],
                              -1)
    assert not np.allclose(gated_short_conv(swapped, taps),
                           _equations(bcu, taps), atol=1e-3)
    assert not np.allclose(gated_short_conv(bcu, taps[::-1]),
                           _equations(bcu, taps), atol=1e-3)


def test_short_conv_is_transformers_lfm2_short_conv():
    """The module against ``Lfm2ShortConv.slow_forward`` (PyTorch's
    ``Conv1d(groups=D, padding=L - 1)`` cut to T) with the same weights."""
    torch = pytest.importorskip("torch")
    lfm2 = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    D = 16
    c = conv_moe_tiny(hidden_size=D)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 10, D))
    params = nn.unbox(ShortConv(c).init(jax.random.PRNGKey(6), x))["params"]
    theirs = lfm2.Lfm2ShortConv(lfm2.Lfm2Config(
        hidden_size=D, conv_L_cache=3, conv_bias=False), layer_idx=0)
    with torch.no_grad():
        theirs.in_proj.weight.copy_(torch.tensor(
            np.asarray(params["in_proj"]["kernel"]).T))
        theirs.out_proj.weight.copy_(torch.tensor(
            np.asarray(params["out_proj"]["kernel"]).T))
        theirs.conv.weight.copy_(torch.tensor(
            np.asarray(params["taps"]).T[:, None, :]))
        want = theirs.slow_forward(torch.tensor(np.asarray(x))).numpy()
    np.testing.assert_allclose(ShortConv(c).apply({"params": params}, x),
                               want, rtol=1e-5, atol=1e-5)


# --- against the reference ------------------------------------------------------

def _system_loss(c, variables, tokens, labels):
    def loss(params):
        out, mutated = ConvMoE(c).apply(
            {**variables, "params": params}, tokens, train=True,
            mutable=["batch_stats"])
        return next_tokens_loss()(out, labels), mutated["batch_stats"]
    return jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])


@pytest.mark.parametrize("over", [
    dict(),
    dict(remat=True, remat_policy="dots_attn"),
    dict(remat=True, remat_policy="full"),
    dict(layer_types=(FULL, CONV, FULL, CONV), num_dense_layers=2,
         num_experts_per_tok=3, routed_scaling_factor=2.0),
    dict(experts_held=2, first_expert=6),
], ids=["plain", "remat_dots_attn", "remat_full", "another_pattern",
        "a_share"])
def test_loss_and_every_gradient_agree_with_the_reference(over):
    c = conv_moe_tiny(**over)
    tokens, labels = _tokens(0, 3, 16)
    variables = _init(c, tokens)
    (loss, _), grads = _system_loss(c, variables, tokens, labels)
    ref = reference.build(reference_config(c))
    biases = {name: jnp.zeros(c.num_experts) for name in ref.expert_layers}
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.jit(jax.value_and_grad(
            ref.loss, has_aux=True))(_flat(variables["params"]), biases,
                                     tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    got = named_leaves(grads)
    assert set(got) == set(want_grads)
    for name, w in want_grads.items():
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(got[name], w, atol=2e-6, rtol=2e-4,
                                   err_msg=name)


def test_the_backward_keeps_the_forwards_products_under_dots_attn():
    """Three expert layers, three held of eight experts at two choices: 96
    pairs, a plan of 72 rows."""
    from horovod_tpu.parallel import moe
    from test_latent_moe import check_products_kept
    tokens, labels = _tokens(0, 3, 16)
    assert moe.plan_rows(2 * 48, 3, 8) == 72

    def build(policy):
        c = conv_moe_tiny(remat=True, remat_policy=policy)
        variables = _init(c, tokens)
        model = ConvMoE(c)

        def loss(params):
            out, _ = model.apply({**variables, "params": params}, tokens,
                                 train=True, mutable=["batch_stats"])
            return next_tokens_loss()(out, labels)
        return loss, variables["params"]
    check_products_kept(build, 3)


def test_the_flash_path_with_qk_norm_agrees_with_the_reference():
    """The attention layer through the Pallas kernels (interpret mode), q and
    k normed a head before them, under the cell's policy that saves the
    kernels' outputs: 96 tokens, one tile."""
    c = conv_moe_tiny(use_flash=True, remat=True, remat_policy="dots_attn")
    tokens, labels = _tokens(4, 1, 96)
    variables = _init(c, tokens)
    text = str(jax.make_jaxpr(lambda p: _system_loss(
        c, {**variables, "params": p}, tokens, labels)[0][0])(
            variables["params"]))
    assert "hvd_flash_fwd" in text and "hvd_flash_bwd" in text
    (loss, _), grads = _system_loss(c, variables, tokens, labels)
    ref = reference.build(reference_config(c))
    biases = {name: jnp.zeros(c.num_experts) for name in ref.expert_layers}
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.jit(jax.value_and_grad(
            ref.loss, has_aux=True))(_flat(variables["params"]), biases,
                                     tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=5e-6)
    got = named_leaves(grads)
    assert set(got) == set(want_grads)
    for name, w in want_grads.items():
        np.testing.assert_allclose(got[name], w, atol=1e-5, rtol=1e-3,
                                   err_msg=name)


def test_the_selection_bias_moves_as_the_references():
    """One step from a bias that is not zero: the choice reads it, the
    counts are the reference's, and each bias moves by the rate against its
    expert's load."""
    c = conv_moe_tiny(bias_update_rate=0.01)
    tokens, labels = _tokens(2, 4, 16)
    variables = _init(c, tokens)
    start = {name: 0.05 * jax.random.normal(jax.random.PRNGKey(i), (8,))
             for i, name in enumerate(("layer_1", "layer_2", "layer_3"))}
    variables["batch_stats"] = {
        name: {**variables["batch_stats"][name],
               "feed_forward": {**variables["batch_stats"][name][
                   "feed_forward"], "bias": b}} for name, b in start.items()}
    (_, stats), _ = _system_loss(c, variables, tokens, labels)
    ref = reference.build(reference_config(c))
    opt_state = ref.init(_flat(variables["params"]))[0]
    with jax.default_matmul_precision("highest"):
        _, (_, biases), _ = jax.jit(ref.step)(
            _flat(variables["params"]), (opt_state, start), (tokens, labels))
    for name, b in biases.items():
        got = stats[name]["feed_forward"]
        np.testing.assert_allclose(got["bias"], b, atol=1e-7)
        assert float(got["counts"].sum()) == 4 * 16 * 2
        assert not np.allclose(b, start[name])


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2 (the cell: 32 as 4 shares of 8): with no
    shared expert nothing is common to the shares, and their parts add up
    to the whole layer as the uncut reference computes it."""
    whole = conv_moe_tiny(experts_held=None, first_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.hidden_size))
    params = nn.unbox(ExpertLayer(whole.expert_layer).init(
        jax.random.PRNGKey(5), x))["params"]
    assert set(params) == {"router", "w1", "w3", "w2"}

    def share(first):
        """The same weights, cut to experts [first, first + 2)."""
        cut = {**params, **{w: params[w][first:first + 2]
                            for w in ("w1", "w3", "w2")}}
        stats = {"bias": jnp.zeros(8), "counts": jnp.zeros(8),
                 "rows": jnp.zeros(2)}
        return ExpertLayer(dataclasses.replace(
            whole, experts_held=2, first_expert=first).expert_layer).apply(
                {"params": cut, "batch_stats": stats}, x)

    parts = [share(first) for first in (0, 2, 4, 6)]
    ref = reference.build(reference_config(whole))
    flat = {"moe/" + k[len("params/"):]: jnp.asarray(v)
            for k, v in named_leaves({"params": params}).items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(x.reshape(32, -1), flat, "moe/",
                              jnp.zeros(8))
    np.testing.assert_allclose(sum(parts).reshape(32, -1), want,
                               atol=1e-5, rtol=1e-5)
    assert all(float(jnp.abs(p).max()) > 1e-2 for p in parts)


def _second_loss(config, variables, tokens, labels):
    """``(first loss, the loss after one AdamW update on the same batch)``,
    as the cell's check reads the system."""
    tx = optax.adamw(1e-2)

    @jax.jit
    def both(params):
        def loss(p):
            out = ConvMoE(config).apply({**variables, "params": p}, tokens,
                                        train=False)
            return next_tokens_loss()(out, labels)
        first, grads = jax.value_and_grad(loss)(params)
        updates, _ = tx.update(grads, tx.init(params), params)
        moved = optax.apply_updates(params, updates)  # hvd-analyze: ok
        return first, loss(moved)
    return [float(x) for x in both(variables["params"])]


@pytest.fixture(scope="module")
def checked():
    """The right program's two losses within 1e-5 of the reference's."""
    c = conv_moe_tiny()
    tokens, labels = _tokens(5, 4, 16)
    variables = _init(c, tokens)
    ref = reference.build(reference_config(c))
    flat = _flat(variables["params"])
    biases = {name: jnp.zeros(c.num_experts) for name in ref.expert_layers}
    loss = jax.jit(lambda p: ref.loss(p, biases, tokens, labels)[0])
    tx = optax.adamw(1e-2)
    with jax.default_matmul_precision("highest"):
        first, g = jax.value_and_grad(loss)(flat)
        up, _ = tx.update(g, tx.init(flat), flat)
        moved = optax.apply_updates(flat, up)  # hvd-analyze: ok
        want = (float(first), float(loss(moved)))
    right = _second_loss(c, variables, tokens, labels)
    assert max(abs(a - b) / b for a, b in zip(right, want)) < 1e-5
    return c, variables, tokens, labels, want


def _mixer(bcu, taps, order=(0, 1, 2), turn=False):
    D = taps.shape[1]
    parts = [bcu[..., i * D:(i + 1) * D] for i in order]
    return gated_short_conv(jnp.concatenate(parts, -1),
                            taps[::-1] if turn else taps)


_ATTENTION = ConvMoEConfig.attention
FAULTS = {
    "taps_turned_round": ("gated_short_conv",
                          lambda bcu, taps: _mixer(bcu, taps, turn=True)),
    "b_and_c_swapped": ("gated_short_conv",
                        lambda bcu, taps: _mixer(bcu, taps, (1, 0, 2))),
    "no_qk_norm": ("attention", property(lambda self: dataclasses.replace(
        _ATTENTION.fget(self), qk_norm=False))),
    "gates_scaled_by_2": dict(routed_scaling_factor=2.0),
    "one_choice_too_few": dict(num_experts_per_tok=1),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_misses_the_reference(fault, checked, monkeypatch):
    """Every wrong program is off the reference by more than a hundred
    times what the right one is, at the first or the second loss."""
    from horovod_tpu.models import conv_moe
    c, variables, tokens, labels, want = checked
    if isinstance(FAULTS[fault], dict):
        c = dataclasses.replace(c, **FAULTS[fault])
    else:
        name, wrong = FAULTS[fault]
        monkeypatch.setattr(ConvMoEConfig if name == "attention"
                            else conv_moe, name, wrong)
    wrong = _second_loss(c, variables, tokens, labels)
    assert max(abs(a - b) / b for a, b in zip(wrong, want)) > 1e-3, wrong


# --- the shared code leaves the other models as they were ------------------------

def test_an_expert_layer_without_a_shared_expert_builds_none():
    """``n_shared_experts == 0``: no ``shared`` parameters and no
    ``hvd_moe_shared`` scope; at 1 the layer is the routed part plus the
    shared SwiGLU, as in the two expert models, which keep theirs."""
    routed_only = conv_moe_tiny().expert_layer
    with_shared = dataclasses.replace(routed_only, n_shared_experts=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32))
    both = nn.unbox(ExpertLayer(with_shared).init(jax.random.PRNGKey(8), x))
    params, stats = both["params"], both["batch_stats"]
    routed = {k: v for k, v in params.items() if k != "shared"}
    assert set(params) - set(routed) == {"shared"}
    assert set(nn.unbox(ExpertLayer(routed_only).init(
        jax.random.PRNGKey(8), x))["params"]) == set(routed)
    y = ExpertLayer(routed_only).apply(
        {"params": routed, "batch_stats": stats}, x)
    shared = SwiGLU(with_shared, 16).apply({"params": params["shared"]}, x)
    np.testing.assert_array_equal(
        ExpertLayer(with_shared).apply(both, x), y + shared)

    def text(layer_config, variables):
        return jax.jit(lambda v, x: ExpertLayer(layer_config).apply(
            v, x)).lower(variables, x).as_text(debug_info=True)
    assert "hvd_moe_shared" in text(with_shared, both)
    assert "hvd_moe_shared" not in text(
        routed_only, {"params": routed, "batch_stats": stats})
    tokens, _ = _tokens(1, 1, 8)
    for model, layers in ((LatentMoE(latent_moe_tiny()), ("layer_1",
                                                           "layer_2")),
                          (WindowMoE(window_moe_tiny()),
                           ("layer_1", "layer_2", "layer_3"))):
        p = jax.eval_shape(lambda t: model.init(
            jax.random.PRNGKey(0), t, train=False), tokens)["params"]
        for name in layers:
            assert "shared" in p[name]["moe"], (model, name)


class _AttentionBefore(nn.Module):
    """``models/llama.py Attention`` as it was before ``qk_norm``, its
    materialised path (the one the CPU takes), op for op."""

    cfg: llama.LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        head_dim = c.dim // c.n_heads
        B, T = x.shape[0], x.shape[1]
        dense = lambda feats, names, name: nn.Dense(
            feats, use_bias=False, dtype=c.dtype, name=name,
            kernel_init=llama._part(nn.initializers.lecun_normal(), names))
        q = dense(c.n_heads * head_dim, ("embed", "heads"), "wq")(x)
        k = dense(c.n_kv_heads * head_dim, ("embed", "kv_heads"), "wk")(x)
        v = dense(c.n_kv_heads * head_dim, ("embed", "kv_heads"), "wv")(x)
        q = q.reshape(B, T, c.n_heads, head_dim)
        k = k.reshape(B, T, c.n_kv_heads, head_dim)
        v = v.reshape(B, T, c.n_kv_heads, head_dim)
        q = nn_partitioning.with_sharding_constraint(
            q, ("batch", "seq", "heads", "head_dim"))
        q = llama.rope(q, positions, c.rope_theta)
        k = llama.rope(k, positions, c.rope_theta)
        rep = c.n_heads // c.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / head_dim ** 0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
        from jax.ad_checkpoint import checkpoint_name
        o = checkpoint_name(jnp.einsum("bhqk,bkhd->bqhd", p, v), "attn_out")
        o = o.reshape(B, T, c.n_heads * head_dim)
        out = nn.Dense(
            c.dim, use_bias=False, dtype=c.dtype, name="wo",
            kernel_init=llama._part(nn.initializers.lecun_normal(),
                                    ("heads", "embed")))(o)
        return nn_partitioning.with_sharding_constraint(
            out, ("batch", "seq", "embed"))


@pytest.mark.parametrize("which", ["llama", "ouro"])
def test_qk_norm_off_is_the_attention_as_it_was(which, monkeypatch):
    """At the tiny presets of the two models that run ``llama.Attention``:
    the same parameters, the same program and the same logits to the bit as
    the attention without the option; with it on, each attention gains a
    per-head ``q_norm`` and ``k_norm`` and nothing else."""
    model = llama.Llama(llama.llama_tiny()) if which == "llama" \
        else looped.Looped(looped.looped_tiny())
    tokens = _tokens(3, 2, 16)[0]
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), tokens,
                                    train=False))
    run = lambda v: model.apply(v, tokens, train=False)
    now, program = run(variables), str(jax.make_jaxpr(run)(variables))
    monkeypatch.setattr(llama, "Attention", _AttentionBefore)
    monkeypatch.setattr(looped, "Attention", _AttentionBefore)
    before = nn.unbox(model.init(jax.random.PRNGKey(0), tokens, train=False))
    assert jax.tree_util.tree_structure(before) == \
        jax.tree_util.tree_structure(variables)
    jax.tree_util.tree_map(np.testing.assert_array_equal, before, variables)
    assert str(jax.make_jaxpr(run)(variables)) == program
    jax.tree_util.tree_map(np.testing.assert_array_equal, run(variables), now)
    monkeypatch.undo()
    if which == "llama":
        cfg = dataclasses.replace(model.cfg, qk_norm=True)
        added = set(named_leaves(nn.unbox(llama.Llama(cfg).init(
            jax.random.PRNGKey(0), tokens, train=False)))) \
            - set(named_leaves(variables))
        assert added == {f"params/block_{i}/attn/{n}_norm/scale"
                         for i in range(cfg.n_layers) for n in "qk"}


# --- through the data-parallel step ------------------------------------------------

def test_conv_layers_carry_their_scopes_and_count_themselves():
    """``hvd_short_conv`` round a conv layer with ``hvd_conv_mix`` inside,
    ``hvd_attn_full`` round the attention layer, the experts' scopes but no
    ``hvd_moe_shared``, and one ``hvd_conv_layers_total{kernel=,width=}`` a
    traced conv layer."""
    hvd.init()
    c = conv_moe_tiny(axis_name=hvd.RANK_AXIS)
    tokens = jnp.asarray(np.random.RandomState(8).randint(0, 128, (8, 16)),
                         jnp.int32)
    dopt = distributed(optax.sgd(0.1))
    model = ConvMoE(c)
    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1], dopt)
    count = lambda: T.active().registry.counter_value(
        "hvd_conv_layers_total", kernel=3, width=32)
    before = count()
    step = make_train_step(model, dopt, next_tokens_loss())
    text = step.lower(state, tokens, tokens).as_text(debug_info=True)
    assert count() - before == 3.0      # three conv layers, traced once
    for scope in ("hvd_short_conv", "hvd_conv_mix", "hvd_attn_full",
                  "hvd_moe_route", "hvd_moe_experts"):
        assert f"/{scope}" in text or f'"{scope}/' in text, scope
    assert "hvd_moe_shared" not in text
    assert "hvd_forward)/ConvMoE/layer_0/conv/hvd_short_conv/" \
        "hvd_conv_mix/" in text
    assert "/layer_1/hvd_attn_full/self_attn/" in text
    state, loss = step(state, tokens, tokens)
    assert np.isfinite(float(loss))


def test_the_config_refuses_what_it_does_not_implement():
    with pytest.raises(ValueError, match="conv_bias"):
        ConvMoEConfig(conv_bias=True)
    with pytest.raises(ValueError, match="conv_bias"):
        ConvMoEConfig(norm_topk_prob=False)
    with pytest.raises(ValueError, match="KV heads"):
        conv_moe_tiny(num_attention_heads=5)
    with pytest.raises(ValueError, match="layer_types"):
        ConvMoE(conv_moe_tiny(layer_types=(CONV, "sliding_attention"))).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), train=False)
