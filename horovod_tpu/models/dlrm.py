"""DLRM: deep learning recommendation model with sharded embedding tables.

Role: BASELINE.md config 5 (DLRM — sparse allgather/allreduce of embedding
tables in the reference; the reference's examples do sparse-gradient
allreduce via allgather of indices+values). TPU-native layout (the public
DLRM-on-TPU recipe): the big embedding tables are MODEL-parallel — sharded
over the ``ep`` axis (table-wise: table i lives on device i mod n) — while
the dense MLPs are data-parallel; the per-batch exchange of looked-up
embedding rows is an all_to_all in the compiled graph, which XLA derives
from the sharding constraints below. Dense/sparse interaction is the
standard pairwise dot-product feature interaction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from .llama import _part


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    num_tables: int = 26
    rows_per_table: int = 100000
    embed_dim: int = 64
    dense_features: int = 13
    bottom_mlp: Sequence[int] = (512, 256, 64)
    top_mlp: Sequence[int] = (512, 256, 1)
    dtype: Any = jnp.float32


def dlrm_criteo() -> DLRMConfig:
    return DLRMConfig()


def dlrm_tiny() -> DLRMConfig:
    return DLRMConfig(num_tables=8, rows_per_table=64, embed_dim=8,
                      dense_features=4, bottom_mlp=(16, 8),
                      top_mlp=(16, 1))


class MLPStack(nn.Module):
    sizes: Sequence[int]
    dtype: Any
    final_act: bool = True

    @nn.compact
    def __call__(self, x):
        for i, s in enumerate(self.sizes):
            x = nn.Dense(s, dtype=self.dtype, name=f"fc{i}",
                         kernel_init=_part(nn.initializers.lecun_normal(),
                                           (None, None)))(x)
            if i < len(self.sizes) - 1 or self.final_act:
                x = nn.relu(x)
        return x


class DLRM(nn.Module):
    """Inputs: dense [B, dense_features] float, sparse [B, num_tables] int
    (one categorical id per table). Output: logit [B]."""

    cfg: DLRMConfig

    @nn.compact
    def __call__(self, dense, sparse, train: bool = True, looked=None):
        c = self.cfg
        # [tables, rows, dim] sharded table-wise over ep — the model-parallel
        # half of the DLRM hybrid.
        tables = self.param("embedding_tables",
                            _part(nn.initializers.normal(0.01),
                                  ("experts", None, None)),
                            (c.num_tables, c.rows_per_table, c.embed_dim),
                            jnp.float32)
        B = dense.shape[0]
        # bottom MLP on dense features (data parallel)
        d = MLPStack(c.bottom_mlp, c.dtype, name="bottom")(
            dense.astype(c.dtype))
        if d.shape[-1] != c.embed_dim:
            raise ValueError("bottom_mlp must end at embed_dim")
        # sparse lookups: one row per table; gather over the table axis.
        # vmap over tables, then constrain so the exchange to batch-sharded
        # layout is one all_to_all. A caller doing SPARSE embedding
        # training (make_sparse_dlrm_step) passes pre-gathered rows via
        # ``looked`` so the tables param stays outside the autodiff path
        # (no dense [T,R,D] gradient tables).
        if looked is None:
            looked = jax.vmap(lambda tab, idx: jnp.take(tab, idx, axis=0),
                              in_axes=(0, 1), out_axes=1)(tables, sparse)
        looked = nn_partitioning.with_sharding_constraint(
            looked, ("batch", None, None))  # [B, tables, dim]
        feats = jnp.concatenate([d[:, None, :], looked.astype(c.dtype)],
                                axis=1)  # [B, 1+tables, dim]
        # pairwise dot-product interaction (upper triangle, no diag)
        inter = jnp.einsum("bnd,bmd->bnm", feats, feats)
        n = feats.shape[1]
        iu, ju = jnp.triu_indices(n, k=1)
        inter = inter[:, iu, ju]  # [B, n*(n-1)/2]
        top_in = jnp.concatenate([d, inter.astype(c.dtype)], axis=1)
        out = MLPStack(c.top_mlp, c.dtype, final_act=False,
                       name="top")(top_in)
        return out[:, 0]


def bce_loss(logits, labels):
    """Binary cross entropy on click labels (the DLRM objective)."""
    logits = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels +
                    jnp.log1p(jnp.exp(-jnp.abs(logits))))


def sparse_adagrad_update(tables_flat, accum_flat, flat_idx, row_grads,
                          lr, eps: float = 1e-7):
    """Adagrad on FLAT embedding tables touching ONLY the looked-up rows.

    The reference's DLRM path ships sparse gradients (allgather of
    indices+values, SURVEY.md §6) precisely because dense updates of
    multi-hundred-MB tables are the bottleneck — the r4 profile
    (profile_dlrm.py) measured ~87% of the DLRM step in dense-gradient
    materialization + dense Adagrad + table copies. Because untouched
    rows have exactly zero gradient, sparse Adagrad restricted to the
    touched rows is NUMERICALLY IDENTICAL to dense ``optax.adagrad``
    (``scale_by_rss`` semantics mirrored below, parity-tested):
    duplicate ids within the batch are collapsed by summation BEFORE the
    accumulator update, as the dense gradient would be.

    Tables are FLAT [T*R, D] (table t's row r at t*R + r): a 2-D shape
    lets the caller pin a row-major jit layout — XLA's entry-layout
    heuristic otherwise picks a gather-friendly transposed layout and
    inserts four whole-table transpose copies per step around the
    scatters (~12 ms/step measured on the chip before the pin; CLAUDE.md,
    the entry-layout trap).

    tables_flat/accum_flat: [N, D]; flat_idx: [K] int; row_grads: [K, D]
    (d loss / d looked-up rows). Returns (tables, accum) updated.
    """
    K = flat_idx.shape[0]
    N = tables_flat.shape[0]
    # collapse duplicate rows: one global sort (flat ids never collide
    # across tables), segment-sum grads into a COMPACT [K, D] workspace
    o = jnp.argsort(flat_idx)
    ids_s = flat_idx[o]
    g_s = row_grads[o]
    head = jnp.concatenate([jnp.ones((1,), bool),
                            ids_s[1:] != ids_s[:-1]])
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1          # [K] in [0,S)
    gsum = jnp.zeros_like(g_s).at[seg].add(g_s)
    # segment -> row id; unused tail segments get N (dropped below).
    # NOTE: asserting indices_are_sorted/unique_indices on the big
    # table/accum scatters (uid can be made strictly increasing AND
    # duplicate-free with distinct OOB tail ids) measured ~7% SLOWER
    # interleaved at the bench config — the hints change XLA's scatter
    # lowering for the worse here; measured and rejected (r4).
    uid = jnp.full((K,), N, flat_idx.dtype).at[seg].set(ids_s)
    acc_rows = accum_flat.at[uid].get(mode="fill", fill_value=0.0)
    acc_new = acc_rows + gsum * gsum
    # optax.scale_by_rss update rule, row-restricted
    inv = jnp.where(acc_new > 0.0, jax.lax.rsqrt(acc_new + eps), 0.0)
    tables2 = tables_flat.at[uid].add(-lr * gsum * inv, mode="drop")
    accum2 = accum_flat.at[uid].set(acc_new, mode="drop")
    return tables2, accum2


def build_sparse_training(model, cfg, mesh, rules, params, *,
                          lr: float = 1e-2, eps: float = 1e-7,
                          acc0: float = 0.1):
    """Complete sparse-embedding training setup — ONE definition of the
    flat tables, PINNED row-major jit layouts, and donation, used by
    `examples/train_dlrm.py` (hand-maintained copies drifted twice
    in r4 review; the layout pin is load-bearing: without it XLA's
    entry-layout heuristic transposes the whole tables around the row
    scatters, 4 × ~666MB copies/step at the criteo config).

    ``params`` is the unboxed full param tree; it is NOT mutated, but its
    ``embedding_tables`` buffer is DONATED into the flat [T*R, D] copy —
    afterwards that entry refers to a deleted buffer (JAX raises a
    donated-buffer error on use), so rebuild the full tree from the
    returned pieces (``{**dense_params, "embedding_tables":
    tables.reshape(T, R, D)}``) for any eval ``model.apply``. Returns
    ``(jitted_step, dense_params, tables_flat, accum_flat, opt_state)``;
    thread the five through ``jitted_step(dense_params, tables, accum,
    opt_state, d, s, y)``.
    """
    import optax
    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding, PartitionSpec as P
    # UNSPECIFIED = "let XLA choose" (None would mean "replicate")
    from jax._src.sharding_impls import UNSPECIFIED as _u

    dense_params = {k: v for k, v in params.items()
                    if k != "embedding_tables"}
    nrows = cfg.num_tables * cfg.rows_per_table
    rowmajor = Format(Layout((0, 1)),
                      NamedSharding(mesh, P("ep") if "ep" in
                                    mesh.axis_names else P()))
    with jax.sharding.set_mesh(mesh):
        tables = jax.jit(lambda t: t.reshape(nrows, cfg.embed_dim),
                         out_shardings=rowmajor, donate_argnums=0)(
            params["embedding_tables"])
        accum = jax.jit(lambda t: jnp.full_like(t, acc0),
                        out_shardings=rowmajor)(tables)
    opt = optax.adagrad(lr, initial_accumulator_value=acc0, eps=eps)
    opt_state = opt.init(dense_params)
    jitted = jax.jit(make_sparse_dlrm_step(model, cfg, opt, lr=lr, eps=eps,
                                           rules=rules),
                     donate_argnums=(0, 1, 2, 3),
                     in_shardings=(_u, rowmajor, rowmajor, _u, _u, _u, _u),
                     out_shardings=(_u, rowmajor, rowmajor, _u, _u))
    return jitted, dense_params, tables, accum, opt_state


def make_sparse_dlrm_step(model, cfg, opt_dense, *, lr: float,
                          eps: float = 1e-7, loss=bce_loss, rules=None):
    """Train step with the reference's sparse-embedding semantics: the
    dense MLPs update through ``opt_dense`` (any optax optimizer), the
    embedding tables through :func:`sparse_adagrad_update` — gradients
    exist only for the [B, T, D] looked-up rows, never as dense [T, R, D]
    tables. Tables ride FLAT as [T*R, D] (see sparse_adagrad_update for
    the layout rationale; callers should pin a row-major layout on the
    tables/accum jit params, as :func:`build_sparse_training` does). Returns
    ``step(dense_params, tables_flat, accum_flat, opt_state, d, s, y) ->
    (dense_params, tables_flat, accum_flat, opt_state, loss)``, jittable
    with all array args donated. On a multi-chip mesh pass the resolved
    logical-axis ``rules`` (``train.rules_for_mesh``) so the model's
    internal sharding constraints stay live — flax silently no-ops them
    outside an ``axis_rules`` scope."""
    import contextlib

    import optax
    T, R, D = cfg.num_tables, cfg.rows_per_table, cfg.embed_dim
    scope = (lambda: nn_partitioning.axis_rules(rules)) if rules \
        else contextlib.nullcontext

    def step(dense_params, tables_flat, accum_flat, opt_state, d, s, y):
        B = s.shape[0]
        fid = (s + (jnp.arange(T, dtype=s.dtype) * R)[None, :]).reshape(-1)
        looked = tables_flat[fid].reshape(B, T, D)

        def loss_of(p, rows):
            with scope():
                out = model.apply(
                    {"params": {**p,
                                "embedding_tables":
                                    tables_flat.reshape(T, R, D)}},
                    d, s, looked=rows)
            return loss(out, y)

        lval, (gdense, grows) = jax.value_and_grad(
            loss_of, argnums=(0, 1))(dense_params, looked)
        updates, opt_state2 = opt_dense.update(gdense, opt_state,
                                               dense_params)
        # hvd-analyze: ok — guard lives in the train.py step wrappers
        dense2 = optax.apply_updates(dense_params, updates)  # hvd-analyze: ok
        tables2, accum2 = sparse_adagrad_update(
            tables_flat, accum_flat, fid, grows.reshape(B * T, D), lr, eps)
        return dense2, tables2, accum2, opt_state2, lval

    return step
