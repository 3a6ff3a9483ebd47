"""MoE expert-bank optimizer levers (optimizer/moe_opt.py, VERDICT r4 #2):
reduced-precision moments, factored/partitioned treatment, deferred
expert updates. Numerics only: no cell of the benchmark measures their
memory traffic (ROADMAP D5)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.optimizer import (adamw_low_precision, every_k,
                                   is_expert_param, moe_adamw,
                                   scale_by_adam_low_precision)


def _params():
    rng = np.random.RandomState(0)
    return {"dense": jnp.asarray(rng.randn(4, 3).astype(np.float32)),
            "moe": {"w1": jnp.asarray(rng.randn(2, 3, 4)
                                      .astype(np.float32))}}


def _grads(seed=1):
    rng = np.random.RandomState(seed)
    return {"dense": jnp.asarray(0.1 * rng.randn(4, 3).astype(np.float32)),
            "moe": {"w1": jnp.asarray(0.1 * rng.randn(2, 3, 4)
                                      .astype(np.float32))}}


def test_low_precision_adam_tracks_f32_adam():
    """bf16-stored moments with stochastic rounding stay close to exact
    f32 Adam over a short run (unbiased store; per-step error ~ bf16 ulp)."""
    params = _params()
    ref = optax.scale_by_adam()
    lp = scale_by_adam_low_precision(mu_dtype=jnp.bfloat16,
                                     nu_dtype=jnp.bfloat16)
    s_ref, s_lp = ref.init(params), lp.init(params)
    for i in range(10):
        g = _grads(i)
        u_ref, s_ref = ref.update(g, s_ref)
        u_lp, s_lp = lp.update(g, s_lp)
    for a, b in zip(jax.tree_util.tree_leaves(u_ref),
                    jax.tree_util.tree_leaves(u_lp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.06, atol=0.02)
    # the stored moments really are bf16
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(s_lp.mu))
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(s_lp.nu))


def test_stochastic_rounding_is_unbiased():
    """Rounding 1 + eps (eps far below the bf16 ulp) many times must
    average back to ~1 + eps; round-to-nearest would give exactly 1."""
    from horovod_tpu.optimizer.moe_opt import _stochastic_round
    x = jnp.full((4096,), 1.0 + 2e-3, jnp.float32)   # bf16 ulp at 1.0: 2^-8
    out = _stochastic_round(jax.random.PRNGKey(0), x, jnp.bfloat16)
    mean = float(np.asarray(out, np.float32).mean())
    assert abs(mean - (1.0 + 2e-3)) < 5e-4, mean
    assert len(np.unique(np.asarray(out, np.float32))) == 2  # straddles


def test_every_k_skips_and_scales():
    """Non-apply steps emit exactly zero updates and leave inner state
    untouched; the k-th step applies the inner update scaled by k."""
    params = _params()
    inner = optax.sgd(1.0)
    tx = every_k(inner, 3)
    state = tx.init(params)
    g = _grads()
    for step in range(1, 7):
        updates, state = tx.update(g, state, params)
        leaves = jax.tree_util.tree_leaves(updates)
        if step % 3 == 0:
            # sgd(1.0) update = -g, scaled by k=3
            for u, gr in zip(leaves, jax.tree_util.tree_leaves(g)):
                np.testing.assert_allclose(np.asarray(u),
                                           -3 * np.asarray(gr), rtol=1e-6)
        else:
            assert all(not np.asarray(u).any() for u in leaves)


def test_every_k_one_is_inner():
    params = _params()
    g = _grads()
    tx = every_k(optax.sgd(0.5), 1, scale=1.0)
    u, _ = tx.update(g, tx.init(params), params)
    for a, b in zip(jax.tree_util.tree_leaves(u),
                    jax.tree_util.tree_leaves(g)):
        np.testing.assert_allclose(np.asarray(a), -0.5 * np.asarray(b),
                                   rtol=1e-6)


def test_is_expert_param_selector():
    assert is_expert_param("layers_0/moe/w1")
    assert is_expert_param("model/moe/w3")
    assert not is_expert_param("model/moe/router/kernel")
    assert not is_expert_param("attn/wq")


@pytest.mark.parametrize("variant", ["adamw", "bf16_nu", "bf16_munu",
                                     "factored", "deferred"])
def test_moe_adamw_variants_train(variant):
    """Every variant trains a toy expert/dense mix: dense params move
    every step; under 'deferred' the expert bank moves only on k-th
    steps."""
    params = _params()
    tx = moe_adamw(1e-2, expert_variant=variant, every=2)
    state = tx.init(params)
    prev_expert = np.asarray(params["moe"]["w1"]).copy()
    moved_at = []
    p = params
    for step in range(1, 5):
        u, state = tx.update(_grads(step), state, p)
        p = optax.apply_updates(p, u)
        now = np.asarray(p["moe"]["w1"])
        if not np.array_equal(now, prev_expert):
            moved_at.append(step)
        prev_expert = now.copy()
        assert np.isfinite(np.asarray(p["dense"])).all()
    if variant == "deferred":
        assert moved_at == [2, 4], moved_at
    else:
        assert moved_at == [1, 2, 3, 4], moved_at


def test_moe_adamw_dense_matches_plain_adamw():
    """The dense subtree under any partitioned variant is EXACT AdamW —
    bit-comparable to optax.adamw on the same grads."""
    params = _params()
    ref = optax.adamw(1e-2)
    tx = moe_adamw(1e-2, expert_variant="bf16_munu")
    s_ref, s_tx = ref.init(params), tx.init(params)
    p_ref, p_tx = params, params
    for step in range(3):
        g = _grads(step)
        u_ref, s_ref = ref.update(g, s_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u_ref)
        u_tx, s_tx = tx.update(g, s_tx, p_tx)
        p_tx = optax.apply_updates(p_tx, u_tx)
    np.testing.assert_array_equal(np.asarray(p_ref["dense"]),
                                  np.asarray(p_tx["dense"]))


def test_deferred_pair_two_program_semantics():
    """deferred_pair: the skip program leaves the expert bank bit-identical
    (pass-through state), the apply program moves it with the k-scaled
    update; dense params move every step under both. Structures are
    interchangeable (one init serves both)."""
    params = _params()
    from horovod_tpu.optimizer import deferred_pair
    pair = deferred_pair(1e-2, every=3)
    opt_a, opt_s = pair.apply, pair.skip
    assert pair.every == 3
    state = opt_a.init(params)
    p = params
    moved_at = []
    for step in range(1, 7):
        tx = opt_a if step % 3 == 0 else opt_s
        u, state = tx.update(_grads(step), state, p)
        prev = np.asarray(p["moe"]["w1"]).copy()
        dense_prev = np.asarray(p["dense"]).copy()
        p = optax.apply_updates(p, u)
        if not np.array_equal(np.asarray(p["moe"]["w1"]), prev):
            moved_at.append(step)
        assert not np.array_equal(np.asarray(p["dense"]), dense_prev)
    assert moved_at == [3, 6], moved_at


def test_deferred_pair_schedule_rejected():
    from horovod_tpu.optimizer import deferred_pair
    with pytest.raises(ValueError, match="constant learning rate"):
        deferred_pair(optax.linear_schedule(1e-3, 1e-4, 100), every=4)


def test_make_gspmd_deferred_train_step_counts():
    """The two-program dispatcher applies the expert bank every k-th call
    on a real (tiny, CPU) GSPMD Mixtral step."""
    import jax
    from horovod_tpu.models.llama import LOGICAL_RULES
    from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny
    from horovod_tpu.optimizer import deferred_pair
    from horovod_tpu.parallel import create_mesh
    from horovod_tpu.train import (create_gspmd_train_state,
                                   make_gspmd_deferred_train_step)

    cfg = mixtral_tiny()
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = Mixtral(cfg)
    pair = deferred_pair(1e-3, every=2)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)))
    state = create_gspmd_train_state(model, pair.apply,
                                     jax.random.PRNGKey(0),
                                     tokens, mesh, LOGICAL_RULES)
    step = make_gspmd_deferred_train_step(model, pair, mesh,
                                          LOGICAL_RULES, donate=False)

    def expert_leaf(st):
        flat, _ = jax.tree_util.tree_flatten_with_path(st.params)
        for path, leaf in flat:
            joined = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                              for k in path).lower()
            if "moe" in joined and joined.rsplit("/", 1)[-1] == "w1":
                return np.asarray(leaf).copy()
        raise AssertionError("no expert leaf found")

    moved = []
    prev = expert_leaf(state)
    for i in range(1, 5):
        state, loss = step(state, tokens)
        now = expert_leaf(state)
        moved.append(not np.array_equal(now, prev))
        prev = now
        assert np.isfinite(float(np.asarray(loss)))
    assert moved == [False, True, False, True], moved


def test_deferred_pair_trains_comparably_to_adamw():
    """Training QUALITY guard for the adopted deferred2 bench optimizer:
    30 steps of tiny-Mixtral under deferred_pair(every=4, 4x-scaled LR)
    must reach a final loss in the same regime as exact AdamW (standard
    MoE practice, but it IS an algorithm change — keep it honest)."""
    import jax
    from horovod_tpu.models.llama import LOGICAL_RULES
    from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny
    from horovod_tpu.optimizer import deferred_pair
    from horovod_tpu.parallel import create_mesh
    from horovod_tpu.train import (create_gspmd_train_state,
                                   make_gspmd_train_step,
                                   make_gspmd_deferred_train_step)

    cfg = mixtral_tiny()
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = Mixtral(cfg)
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 24)))

    def run(make_step, opt_init):
        state = create_gspmd_train_state(model, opt_init,
                                         jax.random.PRNGKey(1), tokens,
                                         mesh, LOGICAL_RULES)
        step = make_step(state)
        losses = []
        for _ in range(30):
            state, loss = step(state, tokens)
            losses.append(float(np.asarray(loss)))
        return losses

    ref_opt = optax.adamw(3e-3)
    ref = run(lambda st: make_gspmd_train_step(
        model, ref_opt, mesh, LOGICAL_RULES, donate=False), ref_opt)
    pair = deferred_pair(3e-3, every=4)
    dfr = run(lambda st: make_gspmd_deferred_train_step(
        model, pair, mesh, LOGICAL_RULES, donate=False), pair.apply)

    assert ref[-1] < ref[0] and dfr[-1] < dfr[0], (ref[:2], dfr[:2])
    # same regime: deferred's final loss within 25% of AdamW's progress
    ref_drop = ref[0] - ref[-1]
    dfr_drop = dfr[0] - dfr[-1]
    assert dfr_drop > 0.75 * ref_drop, (ref_drop, dfr_drop)


def test_gspmd_state_with_factored_and_lowp_variants():
    """create_gspmd_train_state must survive rank-CHANGING optimizer
    states under flax-boxed init: Adafactor's factored v_row/v_col
    inherit the full param's axis names from the box, and
    gspmd_shardings rank-fits those to replicated (r5, train.py
    _fit_rank); the bf16 variant checks the path-label normalization
    (boxed 'value' segments stripped) end to end."""
    from horovod_tpu.models.llama import LOGICAL_RULES
    from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny
    from horovod_tpu.parallel import create_mesh
    from horovod_tpu.train import (create_gspmd_train_state,
                                   make_gspmd_train_step)

    cfg = mixtral_tiny()
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = Mixtral(cfg)
    rng = np.random.RandomState(5)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)))
    for variant in ("factored", "bf16_nu"):
        tx = moe_adamw(1e-3, expert_variant=variant)
        state = create_gspmd_train_state(model, tx, jax.random.PRNGKey(5),
                                         tokens, mesh, LOGICAL_RULES)
        leaves = jax.tree_util.tree_leaves(state.opt_state)
        if variant == "factored":
            # the expert w1 is rank-3 [E,D,M]; Adafactor's factored moments
            # are lower-rank — their presence proves the expert subtree
            # actually routed to Adafactor (not a silent dense fallback)
            # and that _fit_rank survived the boxed-spec mismatch
            assert any(l.ndim in (1, 2) and l.size > 8 for l in leaves), \
                [l.shape for l in leaves][:20]
        else:
            assert any(l.dtype == jnp.bfloat16 for l in leaves), \
                {str(l.dtype) for l in leaves}
        step = make_gspmd_train_step(model, tx, mesh, LOGICAL_RULES,
                                     donate=False)
        state, loss = step(state, tokens)
        assert np.isfinite(float(np.asarray(loss))), variant
