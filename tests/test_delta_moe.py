"""The Kimi-delta / latent attention decoder with group-routed experts
(``models/delta_moe.py``) and its chunked delta rule
(``ops/delta_rule.py``), against their equations and against the plain
reference the benchmark holds (``chipbench/reference/delta_moe.py``), on
seeded weights at a tiny size: the chunked rule against the recurrence one
position at a time (forward and every input's gradient, decays on the bound
and near 0, lengths off the chunk), its causality; the model's logits, loss
and every gradient; the expert shares adding up to the uncut layer; the
published layer pattern; group-limited routing against a plain NumPy
selection. The three shared-code changes leave the other models as they
were (routing without groups, latent attention with its options off, the
short convolution's taps); the scopes and the counter in a traced
data-parallel step."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

import horovod_tpu as hvd
from chipbench.families.delta_moe import MODEL_KEYS
from chipbench.harness import named_leaves
from chipbench.reference import delta_moe as reference
from horovod_tpu.core import telemetry as T
from horovod_tpu.models import conv_moe, latent_moe
from horovod_tpu.models.delta_moe import (KDA, MLA, DeltaMoE, DeltaMoEConfig,
                                          delta_moe_tiny)
from horovod_tpu.models.latent_moe import (ExpertLayer, LatentMoE,
                                           latent_moe_tiny, next_tokens_loss)
from horovod_tpu.ops import delta_rule as rule
from horovod_tpu.optimizer import distributed
from horovod_tpu.parallel import moe
from horovod_tpu.train import create_train_state, make_train_step

OPTIMIZER = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95,
             "eps": 1e-8, "weight_decay": 0.1}
HI = lax.Precision.HIGHEST


# --- the chunked rule against the recurrence -----------------------------------

def _recurrence(q, k, v, g, beta):
    """``S <- (I - beta k k^T) Diag(e^g) S + beta k v^T``, ``o = S^T q``,
    one position at a time from ``S = 0``, float32."""
    def one(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., None] * S
        S = S + beta[..., None, None] * k[..., :, None] * (
            v - jnp.einsum("bhd,bhde->bhe", k, S, precision=HI))[..., None, :]
        return S, jnp.einsum("bhd,bhde->bhe", q, S, precision=HI)

    B, _, H, dk = q.shape
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    S = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(lax.scan(one, S, xs)[1], 0, 1)


def _inputs(seed, T, decay, B=2, H=3, dk=8, dv=6):
    """Unit q and k, a log-decay in ``decay = (low, high)``, beta in (0,
    1)."""
    rng = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    return [jnp.asarray(x, jnp.float32) for x in (
        unit(rng.randn(B, T, H, dk)), unit(rng.randn(B, T, H, dk)),
        rng.randn(B, T, H, dv), rng.uniform(*decay, (B, T, H, dk)),
        rng.uniform(0, 1, (B, T, H)))]


@pytest.mark.parametrize("T", [5, 64, 150])
@pytest.mark.parametrize("decay", [(rule.LOWER, rule.LOWER), (-0.01, 0.0),
                                   (rule.LOWER, 0.0)],
                         ids=["on_the_bound", "near_zero", "mixed"])
def test_the_chunked_rule_is_the_recurrence(T, decay):
    """The output and the gradient of every input, at decays on the e^-5
    bound (where an exponent from a chunk's start would overflow) and near
    1, at lengths below, at and off the chunk; nothing infinite."""
    args = _inputs(T, T, decay)
    ct = jax.random.normal(jax.random.PRNGKey(T), (2, T, 3, 6))
    loss = lambda f: lambda *a: (f(*a) * ct).sum()
    np.testing.assert_allclose(rule.delta_rule(*args), _recurrence(*args),
                               atol=2e-5, rtol=1e-5)
    got = jax.grad(loss(rule.delta_rule), argnums=range(5))(*args)
    want = jax.grad(loss(_recurrence), argnums=range(5))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4, err_msg=name)


def test_heads_in_groups_are_heads_at_once(monkeypatch):
    """The interior made a group of heads at a time (10 heads: 5 groups of
    2) or all at once gives the same output and gradients."""
    args = _inputs(1, 70, (rule.LOWER, 0.0), H=10)
    run = jax.jit(jax.value_and_grad(
        lambda *a: (rule.delta_rule(*a) ** 2).sum(), argnums=range(5)))
    grouped = run(*args)
    monkeypatch.setattr(rule, "HEADS_A_PASS", 10)
    whole = jax.jit(jax.value_and_grad(
        lambda *a: (rule.delta_rule(*a) ** 2).sum(), argnums=range(5)))(
            *args)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), grouped, whole)


def test_the_rule_is_causal():
    """Inputs after t change no output up to t, and send it no gradient."""
    args = _inputs(7, 150, (rule.LOWER, 0.0))
    o = rule.delta_rule(*args)
    for t in (0, 63, 64, 100):
        later = [x.at[:, t + 1:].set(y[:, t + 1:]) for x, y in
                 zip(args, _inputs(8, 150, (rule.LOWER, 0.0)))]
        changed = rule.delta_rule(*later)
        np.testing.assert_allclose(changed[:, :t + 1], o[:, :t + 1],
                                   atol=1e-6, rtol=1e-6)
        assert not np.allclose(changed[:, t + 1:], o[:, t + 1:], atol=1e-3)
        grads = jax.grad(lambda *a: rule.delta_rule(*a)[:, :t + 1].sum(),
                         argnums=range(5))(*args)
        for grad in grads:      # zero but for rounding
            np.testing.assert_allclose(grad[:, t + 1:], 0.0, atol=1e-6)


# --- the model against the reference ---------------------------------------------

def reference_config(c):
    """The configuration file's form of a model config, for the reference:
    the counts held here at the top, the published ones beside them."""
    layers = list(c.layer_indices or range(c.num_hidden_layers))
    return {**{k: getattr(c, k) for k in MODEL_KEYS},
            "num_experts": c.expert_layer.held,
            "published": {"num_experts": c.num_experts},
            "deployment": {"first_expert": c.first_expert, "layers": layers},
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
    """Variables with every vector moved off its initial value, so that each
    norm scale and A_log is seen by the comparison, and ``dt_bias`` drawn
    about 0, where the decay gate is not saturated as at initialisation."""
    variables = nn.unbox(jax.jit(
        lambda r, x: DeltaMoE(c).init(r, x, train=False))(
            jax.random.PRNGKey(seed), tokens[:1]))
    leaves, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    moved = [jax.random.normal(k, x.shape) if path[-1].key == "dt_bias"
             else x + 0.2 * jax.random.normal(k, x.shape) if x.ndim == 1
             else x for (path, x), k in zip(leaves, keys)]
    return {"params": jax.tree_util.tree_unflatten(tree, moved),
            "batch_stats": variables["batch_stats"]}


def _system_loss(c, variables, tokens, labels):
    def loss(params):
        out, mutated = DeltaMoE(c).apply(
            {**variables, "params": params}, tokens, train=True,
            mutable=["batch_stats"])
        return next_tokens_loss()(out, labels), mutated["batch_stats"]
    return jax.jit(jax.value_and_grad(loss, has_aux=True))(
        variables["params"])


def test_the_logits_agree_with_the_reference():
    c = delta_moe_tiny()
    tokens, _ = _tokens(0, 2, 70)
    variables = _init(c, tokens)
    ref = reference.build(reference_config(c))
    biases = {name: jnp.zeros(c.num_experts) for name in ref.expert_layers}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.logits)(_flat(variables["params"]), biases, tokens)
    got = DeltaMoE(c).apply(variables, tokens, train=False)
    # float32 through recurrences of 70 positions whose keys, after the
    # taps' SiLU, are far from orthogonal: within 1e-4 of the logits' scale
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=1e-4)


@pytest.mark.parametrize("over", [
    dict(),
    dict(remat=True, remat_policy="dots_attn"),
    dict(remat=True, remat_policy="full"),
    dict(layer_indices=(0, 2, 3, 4), first_k_dense_replace=1,
         num_experts_per_tok=3, routed_scaling_factor=2.0),
    dict(experts_held=4, first_expert=12, n_group=2, topk_group=1),
], ids=["plain", "remat_dots_attn", "remat_full", "held_layers",
        "a_share_and_groups"])
def test_loss_and_every_gradient_agree_with_the_reference(over):
    c = delta_moe_tiny(**over)
    tokens, labels = _tokens(0, 2, 70)
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
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        # within 3e-3 of each gradient's largest entry: the chunked form's
        # gradient of the log-decay is a reverse running sum over a chunk
        # whose terms cancel, and what is summed from it (A_log, dt_bias,
        # the first layers' weights) carries float32 rounding of the terms'
        # size; the rule alone is within 5e-6 of float64
        np.testing.assert_allclose(got[name], w, atol=3e-3 * scale,
                                   rtol=1e-2, err_msg=name)


def test_the_selection_bias_moves_as_the_references():
    """One step from a bias that is not zero: the group step and the choice
    read it, the counts are the reference's, and each bias moves by the
    rate against its expert's load."""
    c = delta_moe_tiny(bias_update_rate=0.01)
    tokens, labels = _tokens(2, 2, 40)
    variables = _init(c, tokens)
    start = {name: 0.05 * jax.random.normal(jax.random.PRNGKey(i), (16,))
             for i, name in enumerate(("layer_1", "layer_2", "layer_3"))}
    variables["batch_stats"] = {
        name: {**variables["batch_stats"][name], "moe": {
            **variables["batch_stats"][name]["moe"], "bias": b}}
        for name, b in start.items()}
    (_, stats), _ = _system_loss(c, variables, tokens, labels)
    ref = reference.build(reference_config(c))
    opt_state = ref.init(_flat(variables["params"]))[0]
    with jax.default_matmul_precision("highest"):
        _, (_, biases), _ = jax.jit(ref.step)(
            _flat(variables["params"]), (opt_state, start), (tokens, labels))
    for name, b in biases.items():
        got = stats[name]["moe"]
        np.testing.assert_allclose(got["bias"], b, atol=1e-7)
        assert float(got["counts"].sum()) == 2 * 40 * 4
        assert not np.allclose(b, start[name])


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4 (the cell: 512 as 64 shares of 8): the
    shared expert is what every share computes alike, so it is counted
    once, and the routed parts add up to the whole layer as the uncut
    reference computes it, groups and all."""
    whole = delta_moe_tiny(experts_held=None, first_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, whole.hidden_size))
    params = nn.unbox(ExpertLayer(whole.expert_layer).init(
        jax.random.PRNGKey(5), x))["params"]
    assert set(params) == {"router", "w1", "w3", "w2", "shared"}

    def share(first):
        """The same weights, cut to experts [first, first + 4)."""
        cut = {**params, **{w: params[w][first:first + 4]
                            for w in ("w1", "w3", "w2")}}
        stats = {"bias": jnp.zeros(16), "counts": jnp.zeros(16),
                 "rows": jnp.zeros(4)}
        return ExpertLayer(dataclasses.replace(
            whole, experts_held=4, first_expert=first).expert_layer).apply(
                {"params": cut, "batch_stats": stats}, x)

    shared = latent_moe.SwiGLU(whole.expert_layer, 16).apply(
        {"params": params["shared"]}, x)
    parts = [share(first) - shared for first in (0, 4, 8, 12)]
    ref = reference.build(reference_config(whole))
    flat = {"moe/" + k[len("params/"):]: jnp.asarray(v)
            for k, v in named_leaves({"params": params}).items()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(x.reshape(32, -1), flat, "moe/",
                              jnp.zeros(16))
    np.testing.assert_allclose((sum(parts) + shared).reshape(32, -1), want,
                               atol=1e-5, rtol=1e-5)
    assert all(float(jnp.abs(p).max()) > 1e-2 for p in parts)


def test_the_published_layer_pattern():
    """42 layers of a period of six: five delta layers, then a latent one;
    the two leading layers dense. The cell's seven held layers, by their
    published indices, keep one period in its order."""
    c = DeltaMoEConfig()
    kinds = [c.layer_kind(i) for i in range(c.num_hidden_layers)]
    assert kinds == ([KDA] * 5 + [MLA]) * 7
    assert kinds.count(KDA) == 35 and kinds.count(MLA) == 7
    held = dataclasses.replace(c, num_hidden_layers=7,
                               first_k_dense_replace=1,
                               layer_indices=(0, 2, 3, 4, 5, 6, 7))
    assert [held.layer_kind(i) for i in range(7)] == \
        [KDA, KDA, KDA, KDA, MLA, KDA, KDA]


def test_the_config_refuses_what_it_does_not_implement():
    with pytest.raises(ValueError, match="KV heads"):
        DeltaMoEConfig(num_kv_heads_for_linear_attn=8)
    with pytest.raises(ValueError, match="log-decay"):
        DeltaMoEConfig(kda_lower_bound=-6.0)
    with pytest.raises(ValueError, match="head-wise"):
        DeltaMoEConfig(gated_attention_proj_granularity_type="element_wise")
    with pytest.raises(ValueError, match="shared expert"):
        DeltaMoEConfig(moe_shared_expert_intermediate_size=1024)
    with pytest.raises(ValueError, match="layer_indices"):
        delta_moe_tiny(layer_indices=(0, 1))


# --- group-limited routing ------------------------------------------------------

def _numpy_selection(scores, bias, top_k, n_group, topk_group):
    """DeepSeek-V3's noaux_tc choice written out a token at a time."""
    T, E = scores.shape
    size = E // n_group
    choice = np.zeros((T, top_k), np.int64)
    for t in range(T):
        biased = scores[t] + bias
        group_score = [np.sort(biased[i * size:(i + 1) * size])[-2:].sum()
                       for i in range(n_group)]
        kept = np.argsort(group_score)[::-1][:topk_group]
        eligible = np.full(E, -np.inf)
        for i in kept:
            eligible[i * size:(i + 1) * size] = biased[i * size:(i + 1) * size]
        choice[t] = np.argsort(eligible)[::-1][:top_k]
    return choice


def test_group_limited_routing_is_the_numpy_selection():
    """512 experts in 8 groups, the 4 best kept, 8 chosen: the choice and
    the gates, against a selection written out in NumPy."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 512)) * 2
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (512,))
    choice, gates = moe.sigmoid_topk_gates(logits, bias, 8, 2.5, 8, 4)
    scores = np.asarray(jax.nn.sigmoid(logits), np.float64)
    want = _numpy_selection(scores, np.asarray(bias, np.float64), 8, 8, 4)
    np.testing.assert_array_equal(np.sort(np.asarray(choice), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(choice), -1)
    np.testing.assert_allclose(gates, 2.5 * picked
                               / picked.sum(-1, keepdims=True), rtol=1e-6)
    # every token's choice lies in at most 4 groups, and differs from the
    # choice without groups for some tokens
    assert (np.array([len(set(row // 64)) for row in np.asarray(choice)])
            <= 4).all()
    free, _ = moe.sigmoid_topk_gates(logits, bias, 8, 2.5)
    assert not np.array_equal(np.sort(choice, -1), np.sort(free, -1))


@pytest.mark.parametrize("pairs, held, routed, n_group, topk_group, rows", [
    pytest.param(8 * 4096, 8, 512, 8, 4, 2048, id="ling"),
    pytest.param(8 * 4096, 8, 512, 1, 1, 1024, id="ling_without_groups"),
    pytest.param(8 * 8192, 16, 256, 1, 1, 8192, id="joyai"),
    pytest.param(3 * 48, 2, 8, 4, 2, 144, id="the_bound")])
def test_the_plan_covers_every_token_keeping_the_held_group(
        pairs, held, routed, n_group, topk_group, rows):
    """Twice what the held experts get where every token keeps their group:
    the expectation among the ``routed * topk_group / n_group`` experts of
    the kept groups; one group is the plan as it was."""
    assert moe.plan_rows(pairs, held, routed, n_group, topk_group) == rows
    if n_group == 1:
        assert moe.plan_rows(pairs, held, routed) == rows


def test_share_load_reads_the_plan_with_the_routers_groups():
    """1,500 held rows of 32,768 pairs: past the plan without groups
    (1,024 rows), inside the group-limited one (2,048)."""
    counts = np.full(512, 32768 / 512)
    counts[:8] = 1500 / 8
    counts[8:] = (32768 - 1500) / 504
    rows = counts[:8]
    plain = moe.share_load(counts, rows, first=0)
    grouped = moe.share_load(counts, rows, first=0, n_group=8, topk_group=4)
    assert (plain["plan_rows"], plain["small_plan"]) == (1024, False)
    assert (grouped["plan_rows"], grouped["small_plan"]) == (2048, True)


def _gates_before(logits, bias, top_k, scaling):
    """``sigmoid_topk_gates`` as it was before groups, op for op."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, choice = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    choice = moe.checkpoint_name(choice.astype(jnp.int32), moe.ROUTE)
    picked = lax.optimization_barrier(
        moe._chosen(scores, choice, scores.shape[-1]))
    picked = moe.checkpoint_name(picked, moe.ROUTE)
    gates = scaling * picked / picked.sum(-1, keepdims=True)
    return choice, moe.checkpoint_name(gates, moe.ROUTE)


@pytest.mark.parametrize("E, k", [(256, 8), (32, 4), (256, 10)],
                         ids=["joyai", "lfm2", "laguna"])
def test_one_group_is_the_selection_as_it_was(E, k):
    """At ``n_group`` 1 the choice and the gates are the function's before
    groups, to the bit, and so is its program."""
    logits = jax.random.normal(jax.random.PRNGKey(E), (128, E))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(k), (E,))
    now = moe.sigmoid_topk_gates(logits, bias, k, 2.5, 1, 1)
    before = _gates_before(logits, bias, k, 2.5)
    jax.tree_util.tree_map(np.testing.assert_array_equal, now, before)
    assert str(jax.make_jaxpr(lambda l, b: moe.sigmoid_topk_gates(
        l, b, k, 2.5))(logits, bias)) == \
        str(jax.make_jaxpr(lambda l, b: _gates_before(l, b, k, 2.5))(
            logits, bias))


# --- the shared code leaves the other models as they were ------------------------

class _LatentAttentionBefore(nn.Module):
    """``models/latent_moe.py LatentAttention`` as it was before its
    options, its materialised path (the one the CPU takes), op for op."""

    cfg: latent_moe.LatentMoEConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        B, T, _ = x.shape
        H, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
        norm = lambda name: latent_moe.RMSNorm(c.rms_norm_eps, c.dtype,
                                               name=name)
        dense = latent_moe._dense
        rope = latent_moe.rope_interleaved
        with jax.named_scope("hvd_mla"):
            cq = norm("q_norm")(dense(c, c.q_lora_rank, ("embed", None),
                                      "wq_a")(x))
            q = dense(c, H * (dn + dr), (None, "heads"), "wq_b")(cq)
            q = q.reshape(B, T, H, dn + dr)
            kva = dense(c, c.kv_lora_rank + dr, ("embed", None), "wkv_a")(x)
            ckv = norm("kv_norm")(kva[..., :c.kv_lora_rank])
            k_rope = kva[..., c.kv_lora_rank:].reshape(B, T, 1, dr)
            kv = dense(c, H * (dn + dv), (None, "heads"), "wkv_b")(ckv)
            kv = kv.reshape(B, T, H, dn + dv)
            q = jnp.concatenate([
                q[..., :dn], rope(q[..., dn:], positions, c.rope_theta)], -1)
            k = jnp.concatenate([
                kv[..., :dn],
                jnp.broadcast_to(rope(k_rope, positions, c.rope_theta),
                                 (B, T, H, dr))], -1)
            v = kv[..., dn:]
            scale = 1.0 / (dn + dr) ** 0.5
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                jnp.float32) * scale
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None],
                          s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
            from jax.ad_checkpoint import checkpoint_name
            o = checkpoint_name(
                jnp.einsum("bhqk,bkhd->bqhd", p, v), "attn_out")
            return dense(c, c.hidden_size, ("heads", "embed"), "wo")(
                o.reshape(B, T, H * dv))


def test_latent_attention_with_its_options_off_is_as_it_was(monkeypatch):
    """JoyAI's tiny model: the same parameter tree, the same program and
    the same logits to the bit as the attention before its options; with
    them on, the layer gains ``wq`` for ``wq_a``/``q_norm``/``wq_b``, two
    head norms of ``dn + dr`` and a gate of one output a head."""
    model = LatentMoE(latent_moe_tiny())
    tokens = _tokens(3, 2, 16)[0]
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), tokens,
                                    train=False))
    run = lambda v: model.apply(v, tokens, train=False)
    now, program = run(variables), str(jax.make_jaxpr(run)(variables))
    monkeypatch.setattr(latent_moe, "LatentAttention",
                        _LatentAttentionBefore)
    before = nn.unbox(model.init(jax.random.PRNGKey(0), tokens, train=False))
    assert jax.tree_util.tree_structure(before) == \
        jax.tree_util.tree_structure(variables)
    jax.tree_util.tree_map(np.testing.assert_array_equal, before, variables)
    assert str(jax.make_jaxpr(run)(variables)) == program
    jax.tree_util.tree_map(np.testing.assert_array_equal, run(variables), now)
    monkeypatch.undo()
    c = dataclasses.replace(latent_moe_tiny(), q_lora_rank=None,
                            use_qk_norm=True, head_gate=True)
    x = jnp.zeros((1, 4, c.hidden_size))
    shapes = lambda cfg: {k: v.shape for k, v in named_leaves(nn.unbox(
        latent_moe.LatentAttention(cfg).init(
            jax.random.PRNGKey(0), x, jnp.arange(4)[None]))).items()}
    old, new = shapes(latent_moe_tiny()), shapes(c)
    assert set(old) - set(new) == {"params/wq_a/kernel", "params/q_norm/scale",
                                   "params/wq_b/kernel"}
    assert {k: new[k] for k in set(new) - set(old)} == {
        "params/wq/kernel": (32, 2 * 16), "params/q_head_norm/scale": (16,),
        "params/k_head_norm/scale": (16,), "params/wg/kernel": (32, 2)}


def _gated_short_conv_before(bcu, taps):
    """``conv_moe.gated_short_conv`` as it was before its taps were split
    out, op for op."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    v = b * u
    T, L = v.shape[-2], taps.shape[0]
    padded = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(L - 1, 0), (0, 0)])
    z = taps[0] * padded[..., :T, :]
    for j in range(1, L):
        z = z + taps[j] * padded[..., j:j + T, :]
    return c * z


def test_lfm2_logits_are_bit_identical_with_the_taps_shared(monkeypatch):
    model = conv_moe.ConvMoE(conv_moe.conv_moe_tiny())
    tokens = _tokens(4, 2, 16)[0]
    variables = nn.unbox(model.init(jax.random.PRNGKey(0), tokens,
                                    train=False))
    run = lambda v: model.apply(v, tokens, train=False)
    now, program = run(variables), str(jax.make_jaxpr(run)(variables))
    monkeypatch.setattr(conv_moe, "gated_short_conv",
                        _gated_short_conv_before)
    assert str(jax.make_jaxpr(run)(variables)) == program
    np.testing.assert_array_equal(run(variables), now)


def test_the_delta_layer_taps_are_the_shared_causal_taps():
    """Four taps over q, k and v alike: ``z_t = sum_j w_j y_{t-3+j}``."""
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    z = np.zeros((2, 9, 6))
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                z[:, t] += np.asarray(taps[j]) * np.asarray(y[:, t - 3 + j])
    np.testing.assert_allclose(conv_moe.causal_taps(y, taps), z, rtol=1e-5,
                               atol=1e-6)


# --- through the data-parallel step ------------------------------------------------

def test_delta_layers_carry_their_scopes_and_count_themselves():
    """``hvd_kda`` round a delta layer with ``hvd_kda_scan`` inside, forward
    and in the backward's transpose, ``hvd_mla`` and the head gate round
    the latent layer, and one ``hvd_kda_layers_total{heads=, head_dim=,
    chunk=}`` a traced delta layer."""
    hvd.init()
    c = delta_moe_tiny(axis_name=hvd.RANK_AXIS)
    tokens = jnp.asarray(np.random.RandomState(8).randint(0, 128, (8, 16)),
                         jnp.int32)
    dopt = distributed(optax.sgd(0.1))
    model = DeltaMoE(c)
    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1], dopt)
    count = lambda: T.active().registry.counter_value(
        "hvd_kda_layers_total", heads=2, head_dim=8, chunk=rule.CHUNK)
    before = count()
    # one row of 16 tokens a rank: 64 pairs, 3 of 16 experts held, 2 of 4
    # groups kept, so a plan of 48 rows (24 without the groups)
    plan = lambda: T.active().registry.counter_value(
        "hvd_moe_layers_total", held=3, routed=16, top_k=4, plan_rows=48)
    planned = plan()
    step = make_train_step(model, dopt, next_tokens_loss())
    text = step.lower(state, tokens, tokens).as_text(debug_info=True)
    assert count() - before == 3.0      # three delta layers, traced once
    assert plan() - planned == 3.0      # three expert layers
    assert "hvd_forward)/DeltaMoE/layer_0/attn/hvd_kda/" in text
    assert "/hvd_kda/hvd_kda_scan/" in text
    assert "transpose(jvp(hvd_forward))/DeltaMoE/layer_1/attn/hvd_kda/" \
        "hvd_kda_scan/" in text
    assert "/layer_2/attn/hvd_mla/" in text and "/hvd_head_gate/" in text
    state, loss = step(state, tokens, tokens)
    assert np.isfinite(float(loss))
