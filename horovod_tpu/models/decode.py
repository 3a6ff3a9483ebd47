"""Paged KV-cache decode path for the autoregressive models (Llama, Mixtral).

Reference analog: none — SURVEY.md §2 confirms upstream Horovod never served
inference; this is the TPU-native step past the reference (PARITY.md §7).
The design follows the production paged-attention layout
(jax.experimental.pallas.ops.tpu.paged_attention): a preallocated device
pool of fixed-size KV blocks, per-sequence block tables mapping logical
positions to physical blocks, and single-token queries attending against
the gathered pages.

Three jit-once programs per model config:

- **prefill** (one compile per prompt bucket): the full causal forward over
  one padded prompt, capturing every layer's post-RoPE K and raw V and
  bulk-writing them into the slot's blocks. Returns all-position logits so
  the last real position seeds generation (and so parity tests can compare
  against ``model.apply`` directly).
- **decode step** (ONE compile for the serving lifetime): a fixed-width
  slot batch ``[S]`` advances one token. Per layer: project q/k/v for the
  new token, write k/v at ``(table[pos//bs], pos % bs)`` (an S-row scatter —
  per-step writes are tiny; the CLAUDE.md scatter trap is about bulk data
  movement), then read the whole context back with ``jnp.take`` over the
  block tables — the attention READ side is pure gather, and the MoE
  dispatch reuses the sort-based gather-only plan from ``parallel/moe.py``.
  Inactive/stalled slots carry zero-padded block tables, so their writes
  target the reserved null block 0 — and are zero-masked via ``active`` so
  block 0 stays all-zero — while their logits are garbage the engine
  discards (active-mask semantics, no recompile on admit/retire).
- **verify step** (ONE compile per draft width — the engine fixes ONE):
  the decode step widened to a ``[S, K]`` window of candidate tokens per
  slot for speculative decoding. One forward scores all K candidate
  positions: the causal mask inside the window falls out of the same
  ``t <= pos+j`` admission the paged reads already use, K/V writes stay
  the decode step's masked scatter (so the null-block invariant holds for
  masked slots, and rejected candidates' writes are overwritten by the
  next window before any mask can admit them), and greedy
  longest-matching-prefix acceptance on the host makes the emitted stream
  bit-identical to single-token decode (``tests/test_decode_parity.py``).

The math is a pure-jnp mirror of the flax modules (same einsum
formulations, same f32 islands: RMSNorm, attention softmax, router,
lm-head accumulation), operating on the plain params pytree the export
seam (``train.step_builder.export_decode_params``) produces — no flax
``apply`` in the serve path, so remat/scan/sow machinery never enters the
decode program. Handles both checkpoint layouts: unrolled ``block_i`` keys
and scanned ``layers``-stacked ``[L, ...]`` leaves.

**Tensor-parallel variants** (:func:`make_prefill_tp` /
:func:`make_decode_step_tp`): the same programs shard_map-partitioned
over a ``tp`` mesh axis, megatron-style — attention heads and MLP/expert
hidden matrices column-parallel (wq/wk/wv/w1/w3 split on the output dim),
their mates row-parallel (wo/w2 split on the input dim), KV pools sharded
on the head dimension (``[L, n_blocks, bs, n_kv/tp, hd]``), block tables
and slot state replicated. Per layer exactly TWO ``lax.psum`` collectives
ride the wire — one after attention-out, one after MLP/expert-down, both
before the residual add — and nothing else: no permutes, no gathers of
KV across shards (each shard's gather-only page reads stay local; the
CLAUDE.md scatter trap stays honored per shard). Embedding, norms, the
router, and the lm head are replicated, so the greedy argmax is local
and bit-identical on every shard (``tests/test_wire_contracts.py`` pins
the collective count and operand bytes; ``tests/test_decode_parity.py``
pins tp>1 token streams against tp=1).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.moe import sorted_combine, sorted_dispatch, topk_router_sorted
from .llama import LlamaConfig, rope

NULL_BLOCK = 0  #: block 0 is reserved — inactive slots write/read here


def is_moe(cfg: LlamaConfig) -> bool:
    """Mixtral-family configs carry an expert bank (duck-typed so this
    module never imports mixtral.py)."""
    return getattr(cfg, "n_experts", 0) > 0


def init_kv_pools(cfg: LlamaConfig, n_blocks: int,
                  block_size: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Zeroed K and V pools, shape ``[L, n_blocks, block_size, n_kv, hd]``
    in the model compute dtype (block 0 is the null block)."""
    head_dim = cfg.dim // cfg.n_heads
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads, head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def layer_params(params, i: int):
    """Layer ``i``'s param subtree for either checkpoint layout: unrolled
    ``block_i`` keys, or the scanned ``layers`` node with [L, ...]-stacked
    leaves (``i`` is a Python int — the slice is static at trace time)."""
    if "layers" in params:
        return jax.tree.map(lambda leaf: leaf[i], params["layers"]["block"])
    return params[f"block_{i}"]


# -- pure-jnp mirrors of the flax modules ------------------------------------

def _rmsnorm(x, scale, eps, dtype):
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * scale).astype(dtype)


def _dense(x, kernel, dtype):
    return jnp.einsum("...d,df->...f", x.astype(dtype), kernel.astype(dtype))


def _mlp(p, c, x):
    gate = _dense(x, p["w1"]["kernel"], c.dtype)
    up = _dense(x, p["w3"]["kernel"], c.dtype)
    return _dense(jax.nn.silu(gate) * up, p["w2"]["kernel"], c.dtype)


def _moe(p, c, tokens):
    """Gather-only routed expert bank on a flat ``[T, D]`` token batch —
    the same sort-based dispatch plan as models/mixtral.py MoEMLP (the
    one-hot scatter formulation profiled slower than the expert matmuls,
    r4)."""
    E = c.n_experts
    T = tokens.shape[0]
    logits = jnp.einsum("td,de->te", tokens.astype(jnp.float32),
                        p["router"]["kernel"].astype(jnp.float32))
    capacity = max(1, int(c.capacity_factor * c.top_k * T / E))
    r = topk_router_sorted(logits, E, capacity, c.top_k)
    dispatched = sorted_dispatch(tokens, r, E, capacity)
    h = jax.nn.silu(jnp.einsum("ecd,edm->ecm", dispatched,
                               p["w1"].astype(c.dtype)))
    h = h * jnp.einsum("ecd,edm->ecm", dispatched, p["w3"].astype(c.dtype))
    out = jnp.einsum("ecm,emd->ecd", h, p["w2"].astype(c.dtype))
    return sorted_combine(out, r, T).astype(c.dtype)


def _ffn(lp, c, x, moe: bool, axis: Optional[str] = None):
    """The block's second half-residual on ``[..., D]`` activations.

    Under tensor parallelism (``axis`` set) the MLP/expert hidden dim is
    sharded, so the down-projection yields a PARTIAL sum — it is
    all-reduced over ``axis`` before the residual add (the per-layer
    MLP-down collective of the wire contract)."""
    y = _rmsnorm(x, lp["mlp_norm"]["scale"], c.norm_eps, c.dtype)
    if moe:
        flat = y.reshape(-1, y.shape[-1])
        part = _moe(lp["moe"], c, flat).reshape(y.shape)
    else:
        part = _mlp(lp["mlp"], c, y)
    if axis is not None:
        part = jax.lax.psum(part, axis)
    return x + part


def _lm_head(params, c, x):
    if c.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x.astype(c.dtype),
                          params["embedding"].astype(c.dtype),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...d,dv->...v", x.astype(c.dtype),
                      params["lm_head"].astype(c.dtype),
                      preferred_element_type=jnp.float32)


def _attn_prefill(p, c, x, positions, n_heads=None, n_kv=None):
    """Causal attention over the whole (padded) prompt — the training
    formulation verbatim (materialized softmax path of llama.Attention),
    additionally returning the pre-repeat post-RoPE K and raw V for the
    cache. ``n_heads``/``n_kv`` default to the config's counts; the TP
    path passes the per-shard locals (the q/k/v/o kernels it sees are the
    column/row slices, so every shape below stays consistent)."""
    head_dim = c.dim // c.n_heads
    n_heads = n_heads or c.n_heads
    n_kv = n_kv or c.n_kv_heads
    B, T = x.shape[0], x.shape[1]
    q = _dense(x, p["wq"]["kernel"], c.dtype).reshape(
        B, T, n_heads, head_dim)
    k = _dense(x, p["wk"]["kernel"], c.dtype).reshape(
        B, T, n_kv, head_dim)
    v = _dense(x, p["wv"]["kernel"], c.dtype).reshape(
        B, T, n_kv, head_dim)
    q = rope(q, positions, c.rope_theta)
    k = rope(k, positions, c.rope_theta)
    rep = n_heads // n_kv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / head_dim ** 0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(c.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, vr).reshape(
        B, T, n_heads * head_dim)
    return _dense(o, p["wo"]["kernel"], c.dtype), k, v


def _make_prefill(cfg: LlamaConfig, block_size: int, *, shards: int = 1,
                  axis: Optional[str] = None):
    """Prefill body parameterized by shard count: with ``shards > 1`` the
    per-device view sees ``n_heads/shards`` query heads, ``n_kv/shards``
    KV heads, locally-sliced kernels, and a head-sharded pool slice; the
    attention-out and MLP-down partials are psum'd over ``axis``."""
    moe = is_moe(cfg)
    n_heads_l = cfg.n_heads // shards
    n_kv_l = cfg.n_kv_heads // shards

    def prefill(params, k_pool, v_pool, tokens, block_ids):
        T = tokens.shape[1]
        if T % block_size:
            raise ValueError(f"prefill bucket {T} must be a multiple of "
                             f"block_size {block_size}")
        x = jnp.take(params["embedding"], tokens, axis=0).astype(cfg.dtype)
        positions = jnp.arange(T)[None, :]
        ks, vs = [], []
        for i in range(cfg.n_layers):
            lp = layer_params(params, i)
            h, k, v = _attn_prefill(
                lp["attn"], cfg,
                _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps,
                         cfg.dtype),
                positions, n_heads_l, n_kv_l)
            if axis is not None:
                h = jax.lax.psum(h, axis)
            x = _ffn(lp, cfg, x + h, moe, axis)
            ks.append(k[0])
            vs.append(v[0])
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps,
                     cfg.dtype)
        logits = _lm_head(params, cfg, x)
        n_ch = T // block_size
        head_dim = cfg.dim // cfg.n_heads
        shape = (cfg.n_layers, n_ch, block_size, n_kv_l, head_dim)
        k_all = jnp.stack(ks).reshape(shape).astype(k_pool.dtype)
        v_all = jnp.stack(vs).reshape(shape).astype(v_pool.dtype)
        k_pool = k_pool.at[:, block_ids].set(k_all)
        v_pool = v_pool.at[:, block_ids].set(v_all)
        return logits, k_pool, v_pool

    return prefill


def make_prefill(cfg: LlamaConfig, block_size: int):
    """Build the prefill program for ``cfg``: one compile per prompt
    bucket (the bucketed-prefill discipline — compile count is bounded by
    configuration, not traffic).

    ``prefill(params, k_pool, v_pool, tokens[1, T], block_ids[T // bs])
    -> (logits[1, T, V] f32, k_pool, v_pool)`` — K/V for positions
    ``0..T-1`` land in the slot's blocks; positions at or beyond the real
    prompt length hold padding K/V, which is harmless because the decode
    mask only admits ``t <= pos`` and position ``pos`` is rewritten by the
    decode step itself before its first read.
    """
    return _make_prefill(cfg, block_size)


def _make_decode(cfg: LlamaConfig, block_size: int, *, shards: int = 1,
                 axis: Optional[str] = None):
    """Decode-step body parameterized by shard count — same structure as
    :func:`_make_prefill`; every KV page read/write below operates on the
    shard's LOCAL heads, so the gather-only read discipline holds
    per shard with zero cross-shard KV movement."""
    moe = is_moe(cfg)
    head_dim = cfg.dim // cfg.n_heads
    rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / head_dim ** 0.5
    n_heads_l = cfg.n_heads // shards
    n_kv_l = cfg.n_kv_heads // shards

    def decode(params, k_pool, v_pool, tokens, positions, block_tables,
               active):
        S = tokens.shape[0]
        bmax = block_tables.shape[1]
        t_max = bmax * block_size
        x = jnp.take(params["embedding"], tokens, axis=0).astype(cfg.dtype)
        blk = jnp.take_along_axis(
            block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
        off = positions % block_size
        pos2 = positions[:, None]
        mask = jnp.arange(t_max)[None, :] <= positions[:, None]
        for i in range(cfg.n_layers):
            lp = layer_params(params, i)
            ap = lp["attn"]
            h = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps,
                         cfg.dtype)
            q = _dense(h, ap["wq"]["kernel"], cfg.dtype).reshape(
                S, 1, n_heads_l, head_dim)
            k = _dense(h, ap["wk"]["kernel"], cfg.dtype).reshape(
                S, 1, n_kv_l, head_dim)
            v = _dense(h, ap["wv"]["kernel"], cfg.dtype).reshape(
                S, 1, n_kv_l, head_dim)
            q = rope(q, pos2, cfg.rope_theta)[:, 0]
            k = rope(k, pos2, cfg.rope_theta)[:, 0]
            v = v[:, 0]
            # write the new token's K/V (S-row scatter), then READ the
            # whole context back as a gather over the block tables.
            # Masked slots (inactive or stalled) target the null block
            # through their zero-padded tables; their values are zeroed so
            # block 0 stays all-zero — the invariant padded reads rely on.
            act = active[:, None, None]
            k_pool = k_pool.at[i, blk, off].set(
                jnp.where(act, k, 0).astype(k_pool.dtype))
            v_pool = v_pool.at[i, blk, off].set(
                jnp.where(act, v, 0).astype(v_pool.dtype))
            kb = jnp.take(k_pool[i], block_tables, axis=0).reshape(
                S, t_max, n_kv_l, head_dim)
            vb = jnp.take(v_pool[i], block_tables, axis=0).reshape(
                S, t_max, n_kv_l, head_dim)
            # grouped-query form: head h reads kv group h // rep — the
            # same pairing as the training path's jnp.repeat, without
            # materializing the repeated K/V
            qg = q.reshape(S, n_kv_l, rep, head_dim)
            s = jnp.einsum("sgrd,stgd->sgrt", qg, kb).astype(
                jnp.float32) * scale
            s = jnp.where(mask[:, None, None, :], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            o = jnp.einsum("sgrt,stgd->sgrd", pr, vb).reshape(
                S, n_heads_l * head_dim)
            attn_out = _dense(o, ap["wo"]["kernel"], cfg.dtype)
            if axis is not None:
                attn_out = jax.lax.psum(attn_out, axis)
            x = _ffn(lp, cfg, x + attn_out, moe, axis)
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps,
                     cfg.dtype)
        logits = _lm_head(params, cfg, x)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # logits/next_tokens rows for masked slots are garbage the engine
        # discards (it keeps their pending tokens via jnp.where); only the
        # K/V writes above need masking, to preserve the null block.
        return logits, next_tokens, k_pool, v_pool

    return decode


def make_decode_step(cfg: LlamaConfig, block_size: int):
    """Build the single-token decode program for ``cfg`` — ONE compile for
    the serving lifetime (fixed slot width S and block-table width Bmax;
    admit/retire only flips the active mask and table contents).

    ``decode(params, k_pool, v_pool, tokens[S], positions[S],
    block_tables[S, Bmax], active[S])
    -> (logits[S, V] f32, next_tokens[S] i32, k_pool, v_pool)``

    Greedy next tokens are computed on device so the engine can feed them
    straight back without a host round-trip (lint-decode-host-sync).
    """
    return _make_decode(cfg, block_size)


def _make_verify(cfg: LlamaConfig, block_size: int, *, shards: int = 1,
                 axis: Optional[str] = None):
    """Speculative verify body — the decode step widened to a k-token
    window per slot. Same shard parameterization as :func:`_make_decode`;
    the window axis rides every einsum as a batch dim, so the gather-only
    read discipline and the per-layer collective placement are unchanged.
    """
    moe = is_moe(cfg)
    head_dim = cfg.dim // cfg.n_heads
    rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / head_dim ** 0.5
    n_heads_l = cfg.n_heads // shards
    n_kv_l = cfg.n_kv_heads // shards

    def verify(params, k_pool, v_pool, tokens, positions, block_tables,
               active):
        S, K = tokens.shape
        bmax = block_tables.shape[1]
        t_max = bmax * block_size
        x = jnp.take(params["embedding"], tokens, axis=0).astype(cfg.dtype)
        # window position j of slot s sits at logical position pos[s] + j
        pos_k = positions[:, None] + jnp.arange(
            K, dtype=positions.dtype)[None, :]
        blk = jnp.take_along_axis(block_tables, pos_k // block_size, axis=1)
        off = pos_k % block_size
        # Past-context AND in-window causality in ONE mask: context token
        # t is admitted for window row j iff t <= pos+j, and window token
        # j' (written to the pool below at position pos+j') satisfies that
        # exactly when j' <= j.
        mask = jnp.arange(t_max)[None, None, :] <= pos_k[:, :, None]
        for i in range(cfg.n_layers):
            lp = layer_params(params, i)
            ap = lp["attn"]
            h = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.norm_eps,
                         cfg.dtype)
            q = _dense(h, ap["wq"]["kernel"], cfg.dtype).reshape(
                S, K, n_heads_l, head_dim)
            k = _dense(h, ap["wk"]["kernel"], cfg.dtype).reshape(
                S, K, n_kv_l, head_dim)
            v = _dense(h, ap["wv"]["kernel"], cfg.dtype).reshape(
                S, K, n_kv_l, head_dim)
            q = rope(q, pos_k, cfg.rope_theta)
            k = rope(k, pos_k, cfg.rope_theta)
            # Write ALL K candidate positions ([S, K]-row scatter), masked
            # exactly like the decode step: inactive/stalled slots write
            # zeros through their zero-padded tables into the null block.
            # Rejected candidates' K/V DO land in the pool — harmlessly:
            # the engine rewinds ``positions`` to the accepted prefix, the
            # mask never admits a position beyond the rewound ``pos``, and
            # the next window (which always starts at the rewound pos and
            # spans past every stale position) overwrites them before any
            # later row's mask can admit them (tests/test_spec_decode.py
            # pins this across block boundaries).
            act = active[:, None, None, None]
            k_pool = k_pool.at[i, blk, off].set(
                jnp.where(act, k, 0).astype(k_pool.dtype))
            v_pool = v_pool.at[i, blk, off].set(
                jnp.where(act, v, 0).astype(v_pool.dtype))
            kb = jnp.take(k_pool[i], block_tables, axis=0).reshape(
                S, t_max, n_kv_l, head_dim)
            vb = jnp.take(v_pool[i], block_tables, axis=0).reshape(
                S, t_max, n_kv_l, head_dim)
            qg = q.reshape(S, K, n_kv_l, rep, head_dim)
            s = jnp.einsum("skgrd,stgd->skgrt", qg, kb).astype(
                jnp.float32) * scale
            s = jnp.where(mask[:, :, None, None, :], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            o = jnp.einsum("skgrt,stgd->skgrd", pr, vb).reshape(
                S, K, n_heads_l * head_dim)
            attn_out = _dense(o, ap["wo"]["kernel"], cfg.dtype)
            if axis is not None:
                attn_out = jax.lax.psum(attn_out, axis)
            x = _ffn(lp, cfg, x + attn_out, moe, axis)
        x = _rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps,
                     cfg.dtype)
        logits = _lm_head(params, cfg, x)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits, next_tokens, k_pool, v_pool

    return verify


def make_verify_step(cfg: LlamaConfig, block_size: int):
    """Build the speculative k-token verify program for ``cfg`` — ONE
    compile per draft width K (the engine uses one fixed
    ``HOROVOD_DECODE_SPEC_K``, so one compile for the serving lifetime).

    ``verify(params, k_pool, v_pool, tokens[S, K], positions[S],
    block_tables[S, Bmax], active[S])
    -> (logits[S, K, V] f32, next_tokens[S, K] i32, k_pool, v_pool)``

    ``tokens[s, 0]`` is slot ``s``'s pending (already-sampled, not yet
    cached) token at position ``positions[s]``; ``tokens[s, 1:]`` are the
    host-drafted candidates at the following positions. Row ``j`` of
    ``next_tokens[s]`` is the model's greedy continuation after consuming
    window tokens ``0..j`` — so ``next_tokens[s, 0]`` is always the TRUE
    next token, and draft ``tokens[s, j+1]`` is accepted exactly when it
    equals ``next_tokens[s, j]`` with every earlier draft accepted (the
    lossless longest-matching-prefix rule; the engine applies it on host
    where the drafts already live). A caller that never accepts drafts
    reads ``next_tokens[:, 0]`` and gets the plain decode step's stream.
    """
    return _make_verify(cfg, block_size)


# -- tensor-parallel (tp) decode plane ---------------------------------------

def validate_tp(cfg: LlamaConfig, tp: int) -> None:
    """The divisibility contract for the megatron-style plan: query and KV
    heads split the head dim, the MLP/expert hidden dim splits its
    matrices. ``tp=1`` is always valid (the unsharded programs)."""
    if tp <= 1:
        return
    for name, value in (("n_heads", cfg.n_heads),
                        ("n_kv_heads", cfg.n_kv_heads),
                        ("hidden_dim", cfg.hidden_dim)):
        if value % tp:
            raise ValueError(
                f"tp={tp} does not divide cfg.{name}={value}")


def kv_pool_spec(axis: str = "tp") -> P:
    """PartitionSpec of the paged KV pools under tensor parallelism:
    ``[L, n_blocks, block_size, n_kv{sharded}, head_dim]`` — block
    geometry replicated, heads split."""
    return P(None, None, None, axis, None)


def decode_param_specs(cfg: LlamaConfig, params, axis: str = "tp"):
    """PartitionSpec pytree mirroring ``params`` for the megatron plan:
    wq/wk/wv and MLP/expert up-projections column-parallel (output dim),
    wo and down-projections row-parallel (input dim), everything else —
    embedding, norms, router, lm head — replicated. Handles both the
    unrolled ``block_i`` and scanned ``layers`` checkpoint layouts (the
    scanned ``[L, ...]`` leaves get a leading ``None``)."""
    def block_specs(block, pfx):
        col = P(*pfx, None, axis)
        row = P(*pfx, axis, None)
        specs = {}
        for key, sub in block.items():
            if key == "attn":
                specs[key] = {"wq": {"kernel": col}, "wk": {"kernel": col},
                              "wv": {"kernel": col}, "wo": {"kernel": row}}
            elif key == "mlp":
                specs[key] = {"w1": {"kernel": col}, "w3": {"kernel": col},
                              "w2": {"kernel": row}}
            elif key == "moe":
                specs[key] = {"router": {"kernel": P()},
                              "w1": P(*pfx, None, None, axis),
                              "w3": P(*pfx, None, None, axis),
                              "w2": P(*pfx, None, axis, None)}
            else:                          # norms and future replicated bits
                specs[key] = jax.tree.map(lambda _: P(), sub)
        return specs

    specs = {}
    for key, sub in params.items():
        if key == "layers":
            specs[key] = {"block": block_specs(sub["block"], (None,))}
        elif key.startswith("block_"):
            specs[key] = block_specs(sub, ())
        else:                              # embedding / final_norm / lm_head
            specs[key] = jax.tree.map(lambda _: P(), sub)
    return specs


def decode_leaf_shard_axis(path_names: Sequence[Any], shape,
                           tp: int) -> Optional[int]:
    """Which array axis of a decode-params leaf the tp plan splits, or
    ``None`` if the leaf is replicated (or indivisible). Keyed on the
    trailing path names so it works for both checkpoint layouts — this is
    the single source of truth the per-shard CAS layer (publisher shard
    plans, registry shard selectors) derives byte movement from."""
    names = tuple(str(n) for n in path_names)
    if not names:
        return None
    leaf, parent = names[-1], (names[-2] if len(names) >= 2 else None)
    if leaf == "kernel" and parent in ("wq", "wk", "wv", "w1", "w3"):
        ax = len(shape) - 1                # column-parallel: output dim
    elif leaf == "kernel" and parent in ("wo", "w2"):
        ax = len(shape) - 2                # row-parallel: input dim
    elif leaf in ("w1", "w3") and parent == "moe":
        ax = len(shape) - 1                # [.., E, D, M]: expert hidden
    elif leaf == "w2" and parent == "moe":
        ax = len(shape) - 2                # [.., E, M, D]: expert hidden
    else:
        return None
    return ax if (tp > 0 and shape[ax] % tp == 0) else None


def _shard_mapped(cfg, mesh, axis, body, n_pools, n_extra, n_outs):
    """Wrap ``body`` in shard_map lazily — the param PartitionSpec tree
    needs the concrete params structure, so construction happens on first
    call (and jit caches the result by tracing, not by wrapper identity)."""
    from jax import shard_map
    pool_s = kv_pool_spec(axis)

    def wrapped(params, *args):
        specs = decode_param_specs(cfg, params, axis)
        in_specs = (specs,) + (pool_s,) * n_pools + (P(),) * n_extra
        out_specs = (P(),) * (n_outs - n_pools) + (pool_s,) * n_pools
        sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        return sm(params, *args)

    return wrapped


def make_prefill_tp(cfg: LlamaConfig, block_size: int, mesh,
                    axis: str = "tp"):
    """:func:`make_prefill` partitioned over ``mesh[axis]``. Same
    signature and semantics; the pools are the head-sharded global views
    (:func:`kv_pool_spec`), params follow :func:`decode_param_specs`,
    tokens/block_ids and the returned logits are replicated."""
    tp = mesh.shape[axis]
    validate_tp(cfg, tp)
    body = _make_prefill(cfg, block_size, shards=tp, axis=axis)
    return _shard_mapped(cfg, mesh, axis, body, n_pools=2, n_extra=2,
                         n_outs=3)


def make_decode_step_tp(cfg: LlamaConfig, block_size: int, mesh,
                        axis: str = "tp"):
    """:func:`make_decode_step` partitioned over ``mesh[axis]``. The wire
    contract: exactly ``2 * n_layers`` all-reduces of ``[S, D]``
    activations (attention-out + MLP/expert-down) and NOTHING else — no
    collective-permutes, no cross-shard KV gathers; slot state, tables,
    logits, and next_tokens stay replicated so the engine's host logic is
    mesh-agnostic (``tests/test_wire_contracts.py`` pins this)."""
    tp = mesh.shape[axis]
    validate_tp(cfg, tp)
    body = _make_decode(cfg, block_size, shards=tp, axis=axis)
    return _shard_mapped(cfg, mesh, axis, body, n_pools=2, n_extra=4,
                         n_outs=4)


def make_verify_step_tp(cfg: LlamaConfig, block_size: int, mesh,
                        axis: str = "tp"):
    """:func:`make_verify_step` partitioned over ``mesh[axis]``. The wire
    contract is the decode step's, re-pinned at the window width: exactly
    ``2 * n_layers`` all-reduces of the ``[S, K, D]`` (= ``S·K × D``
    bytes) activations and NOTHING else — zero collective-permutes, zero
    cross-shard KV movement (``tests/test_wire_contracts.py``
    ``test_tp_verify_wire_contract`` pins count, operand bytes, and the
    absence of permutes)."""
    tp = mesh.shape[axis]
    validate_tp(cfg, tp)
    body = _make_verify(cfg, block_size, shards=tp, axis=axis)
    return _shard_mapped(cfg, mesh, axis, body, n_pools=2, n_extra=4,
                         n_outs=4)
