"""Model-family tests on multi-axis CPU meshes: Llama (dp×sp×tp), Mixtral
(dp×ep), BERT (dp×tp), DLRM (dp×ep) — each trains a few steps with the GSPMD
harness and, for Llama, checks tp-sharded == single-device parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models.llama import LOGICAL_RULES, Llama, llama_tiny
from horovod_tpu.parallel import create_mesh
from horovod_tpu.train import (create_gspmd_train_state,
                               make_gspmd_train_step, next_token_loss)

N = 8


def toks(batch=4, seq=32, vocab=255, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, vocab, (batch, seq)))


def train_losses(model, mesh, steps=3, aux_weight=0.0, rules=LOGICAL_RULES,
                 tokens=None, lr=1e-3, seed=0):
    opt = optax.adamw(lr)
    tokens = toks() if tokens is None else tokens
    state = create_gspmd_train_state(model, opt, jax.random.PRNGKey(seed),
                                     tokens, mesh, rules)
    step = make_gspmd_train_step(model, opt, mesh, rules,
                                 aux_weight=aux_weight)
    out = []
    for _ in range(steps):
        state, loss = step(state, tokens)
        out.append(float(loss))
    return out, state


def test_llama_trains_dp_sp_tp():
    losses, state = train_losses(Llama(llama_tiny()),
                                 create_mesh({"dp": 2, "sp": 2, "tp": 2}))
    assert losses[-1] < losses[0]
    w1 = state.params["block_0"]["mlp"]["w1"]["kernel"]
    assert "tp" in str(w1.sharding.spec)


def test_llama_trains_dp_fsdp_zero3_sharding():
    """dp2 x fsdp4: params/opt state shard over fsdp (ZeRO-3 role — XLA
    inserts allgather-on-use + reducescatter-on-grad), loss matches the
    dp-only mesh bit-for-bit at tolerance (sharding never changes math)."""
    t = toks()
    losses, state = train_losses(Llama(llama_tiny()),
                                 create_mesh({"dp": 2, "fsdp": 4}),
                                 tokens=t)
    assert losses[-1] < losses[0]
    w1 = state.params["block_0"]["mlp"]["w1"]["kernel"]
    assert "fsdp" in str(w1.sharding.spec)     # param is ZeRO-sharded
    base, _ = train_losses(
        Llama(llama_tiny()),
        create_mesh({"dp": 1}, devices=jax.devices()[:1]), tokens=t)
    np.testing.assert_allclose(losses, base, rtol=2e-4)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_llama_context_parallel_attention_matches_dense(impl):
    """attention_impl='ring'/'ulysses' on a dp2 x sp4 mesh: the manual
    context-parallel attention (shard_map island inside the GSPMD step)
    trains and matches the dense XLA-sp path losses."""
    t = toks(batch=2, seq=32)
    mesh = create_mesh({"dp": 2, "sp": 4})
    dense, _ = train_losses(Llama(llama_tiny()), mesh, tokens=t)
    cfg = dataclasses.replace(llama_tiny(), attention_impl=impl)
    cp, state = train_losses(Llama(cfg), mesh, tokens=t)
    np.testing.assert_allclose(cp, dense, rtol=3e-4)
    assert cp[-1] < cp[0]


def test_mixtral_ring_attention_with_expert_parallel():
    """Ring attention composes with MoE expert dispatch: dp2 x sp2 x ep2
    mesh, attention_impl='ring' — the shard_map attention island and the
    alltoall expert exchange live in one compiled step."""
    from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny

    t = toks(batch=2, seq=32)
    mesh = create_mesh({"dp": 2, "sp": 2, "ep": 2})
    cfg = dataclasses.replace(mixtral_tiny(), attention_impl="ring")
    losses, _ = train_losses(Mixtral(cfg), mesh, tokens=t,
                             aux_weight=cfg.router_aux_weight)
    dense, _ = train_losses(Mixtral(mixtral_tiny()), mesh, tokens=t,
                            aux_weight=mixtral_tiny().router_aux_weight)
    np.testing.assert_allclose(losses, dense, rtol=3e-4)


def test_llama_parity_across_meshes():
    """Same seed, same data: dp8 mesh == dp2×sp2×tp2 mesh == 1-device.
    Sharding must never change the math."""
    t = toks()
    base, _ = train_losses(
        Llama(llama_tiny()),
        create_mesh({"dp": 1}, devices=jax.devices()[:1]), tokens=t)
    dp8, _ = train_losses(Llama(llama_tiny()), create_mesh({"dp": 8}),
                          tokens=t)
    mix, _ = train_losses(Llama(llama_tiny()),
                          create_mesh({"dp": 2, "sp": 2, "tp": 2}), tokens=t)
    np.testing.assert_allclose(dp8, base, rtol=2e-4)
    np.testing.assert_allclose(mix, base, rtol=2e-4)


def test_llama_scan_remat_variant():
    cfg = llama_tiny()
    import dataclasses
    cfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    losses, state = train_losses(Llama(cfg), create_mesh({"dp": 4, "tp": 2}))
    assert losses[-1] < losses[0]
    # scanned params carry the layer axis
    w1 = state.params["layers"]["block"]["mlp"]["w1"]["kernel"]
    assert w1.shape[0] == cfg.n_layers


def test_scan_layers_auto_resolution():
    """``scan_layers="auto"`` (the r6 default) unrolls small models and
    scans deep ones; explicit True/False always wins. The choice is
    checkpoint-visible (scan stacks params under "layers"), so the
    threshold is a module constant, not a heuristic."""
    import dataclasses
    from horovod_tpu.models.llama import (SCAN_LAYERS_AUTO_THRESHOLD,
                                          resolve_scan_layers)
    auto = dataclasses.replace(llama_tiny(), scan_layers="auto")
    assert not resolve_scan_layers(auto)          # 2 layers -> unrolled
    deep = dataclasses.replace(auto, n_layers=SCAN_LAYERS_AUTO_THRESHOLD + 1)
    assert resolve_scan_layers(deep)
    at = dataclasses.replace(auto, n_layers=SCAN_LAYERS_AUTO_THRESHOLD)
    assert not resolve_scan_layers(at)            # boundary stays unrolled
    assert resolve_scan_layers(
        dataclasses.replace(auto, scan_layers=True))
    assert not resolve_scan_layers(
        dataclasses.replace(deep, scan_layers=False))


def test_llama_remat_policies_match_full():
    """The named-save policies (r4: "attn"/"dots_attn" keep the flash
    kernel's (o, m, l) residuals so the backward skips the fwd-kernel
    re-run) must be
    numerically identical to "full" remat: same loss trajectory on the
    same init, flash forced on (interpret-mode Pallas on CPU)."""
    import dataclasses
    base = dataclasses.replace(llama_tiny(), scan_layers=True, remat=True,
                               use_flash=True)
    t = toks()
    mesh = create_mesh({"dp": 8})
    ref, _ = train_losses(
        Llama(dataclasses.replace(base, remat_policy="full")), mesh,
        tokens=t)
    for pol in ("dots", "dots_attn", "attn"):
        got, _ = train_losses(
            Llama(dataclasses.replace(base, remat_policy=pol)), mesh,
            tokens=t)
        np.testing.assert_allclose(got, ref, rtol=1e-5,
                                   err_msg=f"policy {pol}")


def test_mixtral_trains_dp_ep():
    from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny
    cfg = mixtral_tiny()
    losses, state = train_losses(Mixtral(cfg),
                                 create_mesh({"dp": 2, "ep": 4}),
                                 aux_weight=cfg.router_aux_weight)
    assert losses[-1] < losses[0]
    assert "ep" in str(state.params["block_0"]["moe"]["w1"].sharding.spec)


def test_bert_trains_dp_tp():
    from horovod_tpu.models.bert import Bert, bert_tiny, mlm_loss
    cfg = bert_tiny()
    model = Bert(cfg)
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 255, (4, 32)))
    labels = jnp.asarray(rng.randint(0, 255, (4, 32)))
    mask = jnp.asarray(rng.rand(4, 32) < 0.15)
    mesh = create_mesh({"dp": 4, "tp": 2})
    opt = optax.adamw(1e-3)

    def loss_fn(logits, _tokens):
        return mlm_loss(logits, labels, mask)

    state = create_gspmd_train_state(model, opt, jax.random.PRNGKey(0),
                                     tokens, mesh, LOGICAL_RULES)
    step = make_gspmd_train_step(model, opt, mesh, LOGICAL_RULES,
                                 loss_fn=loss_fn)
    losses = []
    for _ in range(3):
        state, loss = step(state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_dlrm_trains_dp_ep():
    from horovod_tpu.models.dlrm import DLRM, bce_loss, dlrm_tiny
    cfg = dlrm_tiny()
    model = DLRM(cfg)
    rng = np.random.RandomState(2)
    B = 16
    dense = jnp.asarray(rng.randn(B, cfg.dense_features).astype(np.float32))
    sparse = jnp.asarray(rng.randint(0, cfg.rows_per_table,
                                     (B, cfg.num_tables)))
    labels = jnp.asarray((rng.rand(B) < 0.3).astype(np.float32))
    mesh = create_mesh({"dp": 2, "ep": 4})
    opt = optax.adagrad(1e-2)

    from flax.linen import partitioning as nn_partitioning
    from horovod_tpu.train import rules_for_mesh
    import flax.linen as nn
    rules = rules_for_mesh(mesh, LOGICAL_RULES)
    with nn_partitioning.axis_rules(rules):
        abs_vars = jax.eval_shape(model.init, jax.random.PRNGKey(0), dense,
                                  sparse)
    sharding = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abs_vars["params"]), mesh, rules)

    with jax.sharding.set_mesh(mesh):
        def init_fn(rng):
            with nn_partitioning.axis_rules(rules):
                return model.init(rng, dense, sparse)["params"]
        params = nn.meta.unbox(jax.jit(
            init_fn, out_shardings=sharding)(jax.random.PRNGKey(0)))
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            def loss_of(p):
                with nn_partitioning.axis_rules(rules):
                    logits = model.apply({"params": p}, dense, sparse)
                return bce_loss(logits, labels)
            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            return optax.apply_updates(  # hvd-analyze: ok — test loop
                params, updates), opt_state2, loss

        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert "ep" in str(params["embedding_tables"].sharding.spec)


def test_dlrm_sparse_layout_pin_budget():
    """The ``Format(Layout((0, 1)), ...)`` entry-layout pins in
    ``build_sparse_training`` are load-bearing: without them XLA's
    entry-layout heuristic transposes the WHOLE embedding tables around
    the row gathers/scatters (4 × ~666 MB copies/step at the criteo
    config, r4). Regression rail, declared as the ``dlrm-layout-pin``
    contract: the compiled sparse step contains ZERO transpose/copy
    instructions at the table shape (full or per-shard), and the overall
    copy/transpose counts stay under a pinned bound (observed 51/17 on
    the 8-dev CPU mesh, budget 102/34)."""
    from horovod_tpu.analysis import contracts

    findings = contracts.check_family("dlrm-layout-pin")
    assert not findings, "\n".join(f.format() for f in findings)


def test_dlrm_sparse_step_matches_dense_adagrad():
    """The sparse embedding path (r4: only looked-up rows update — the
    reference's sparse-gradient DLRM semantics) is numerically identical
    to dense optax.adagrad over the whole table, because untouched rows
    have exactly zero gradient. Duplicate ids within a batch are
    deliberately present to exercise the collapse-by-summation."""
    from horovod_tpu.models.dlrm import (DLRM, bce_loss, dlrm_tiny,
                                         make_sparse_dlrm_step)
    cfg = dlrm_tiny()
    model = DLRM(cfg)
    rng = np.random.RandomState(5)
    B = 16
    dense = jnp.asarray(rng.randn(B, cfg.dense_features).astype(np.float32))
    # small id range -> guaranteed duplicate rows per table in the batch
    sparse = jnp.asarray(rng.randint(0, 4, (B, cfg.num_tables)))
    labels = jnp.asarray((rng.rand(B) < 0.3).astype(np.float32))
    lr, eps, acc0 = 1e-2, 1e-7, 0.1

    import flax.linen as nn
    params0 = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), dense, sparse)["params"])

    # dense path: one optimizer over everything
    opt = optax.adagrad(lr, initial_accumulator_value=acc0, eps=eps)
    p = jax.tree_util.tree_map(lambda a: a, params0)
    st = opt.init(p)

    def dense_step(p, st):
        def loss_of(pp):
            return bce_loss(model.apply({"params": pp}, dense, sparse),
                            labels)
        loss, g = jax.value_and_grad(loss_of)(p)
        up, st2 = opt.update(g, st, p)
        return optax.apply_updates(p, up), st2, loss  # hvd-analyze: ok

    # sparse path: tables split out, FLAT [T*R, D] (see
    # sparse_adagrad_update's layout rationale)
    dp = {k: v for k, v in params0.items() if k != "embedding_tables"}
    tables = params0["embedding_tables"].reshape(-1, cfg.embed_dim)
    accum = jnp.full_like(tables, acc0)
    opt_d = optax.adagrad(lr, initial_accumulator_value=acc0, eps=eps)
    st_d = opt_d.init(dp)
    step = jax.jit(make_sparse_dlrm_step(model, cfg, opt_d, lr=lr, eps=eps))

    for _ in range(3):
        p, st, dloss = dense_step(p, st)
        dp, tables, accum, st_d, sloss = step(dp, tables, accum, st_d,
                                              dense, sparse, labels)
        np.testing.assert_allclose(float(dloss), float(sloss), rtol=1e-6)

    np.testing.assert_allclose(
        np.asarray(p["embedding_tables"]).reshape(-1, cfg.embed_dim),
        np.asarray(tables), rtol=1e-5, atol=1e-7)
    for k in dp:
        for a, b in zip(jax.tree_util.tree_leaves(p[k]),
                        jax.tree_util.tree_leaves(dp[k])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_bert_flash_matches_naive():
    """use_flash=True (interpret-mode Pallas) must agree with the
    materialised-softmax path, including the padding mask."""
    import numpy as np

    from horovod_tpu.models.bert import Bert, bert_tiny

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 256, (2, 48)))
    mask = jnp.asarray([[True] * 48, [True] * 30 + [False] * 18])
    m_naive = Bert(bert_tiny())
    m_flash = Bert(dataclasses.replace(bert_tiny(), use_flash=True))
    variables = m_naive.init(jax.random.PRNGKey(0), tokens, mask,
                             train=False)
    a = m_naive.apply(variables, tokens, mask, train=False)
    b = m_flash.apply(variables, tokens, mask, train=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


def test_llama_flash_matches_naive():
    import numpy as np

    from horovod_tpu.models.llama import Llama, llama_tiny

    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 256, (2, 40)))
    cfg = llama_tiny()
    m_naive = Llama(cfg)
    m_flash = Llama(dataclasses.replace(cfg, use_flash=True))
    variables = m_naive.init(jax.random.PRNGKey(0), tokens)
    a = m_naive.apply(variables, tokens)
    b = m_flash.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-3,
                               atol=2e-3)
