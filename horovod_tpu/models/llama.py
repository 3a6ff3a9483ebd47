"""Llama-family decoder transformer, GSPMD-sharded (flagship model).

Role: BASELINE.md config 3 (Llama-3 8B fine-tune — grad allreduce + Adasum
over ICI rings). The reference has no model zoo of its own (it wraps user
models); this framework ships the models its benchmark configs name, built
TPU-first:

- bf16 compute everywhere, fp32 params/optimizer (MXU-native);
- parallelism by **sharding annotation, not code**: params carry logical
  axis names (flax ``with_logical_partitioning``); activations get logical
  constraints; a rule table maps logical axes → mesh axes (dp/fsdp/sp/tp),
  and XLA inserts the collectives (psum for the DP grad sync, all-gathers
  for fsdp, partial-sum psums for tp) — the scaling-book recipe;
- ``lax.scan`` over layers + ``nn.remat`` for compile time and HBM;
- GQA attention with RoPE; causal mask; SwiGLU MLP.

For explicit-collective sequence parallelism (ring/Ulysses attention over an
``sp`` axis) see ``horovod_tpu.parallel``; the GSPMD path shards the
sequence axis of activations directly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning
from ..parallel.moe import PRODUCTS, ROUTE
from ._flash import resolve_flash as _resolve_flash

# Logical → mesh axis rules (see parallel/mesh.py for axis vocabulary).
LOGICAL_RULES = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("vocab", "tp"),
    # ZeRO-3 role: params' embed dim shards over fsdp (sharded at rest;
    # XLA inserts allgather-on-use / reducescatter-on-grad). Activations
    # are unaffected: their specs already consume fsdp via "batch", and
    # flax drops a rule whose mesh axis is taken within the same spec.
    ("embed", "fsdp"),
    ("embed_fsdp", "fsdp"),
    ("embed_table", None),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("experts", "ep"),
    ("layers", None),
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "dots": save matmul outputs, recompute elementwise (the standard TPU
    # trade — elementwise recompute is HBM-cheap, matmuls are not).
    # "full": save nothing inside the block.
    remat_policy: str = "dots"
    # scan_layers=True compiles fast (one traced layer) but trains slower:
    # the scan's loop-carried [L,...] gradient stacks cost a
    # dynamic-update-slice write-back per weight per layer per step —
    # measured 12.6% of the Llama step, and +13% / +22% / +14.5%
    # throughput from unrolling at toy-width Llama / Mixtral / long-context
    # configs (builders' chip runs before PR 1, no cell). Prefer False for
    # production training runs when the ~3x compile time is acceptable.
    # "auto" (the default): unroll when n_layers is small enough to
    # compile fast (≤ SCAN_LAYERS_AUTO_THRESHOLD), scan above it —
    # small/test configs get the throughput win for free, big configs
    # keep bounded compile time. NOTE: the choice is checkpoint-visible
    # (scan stacks params [L,...] under one "layers" node; unrolled uses
    # block_0..block_{L-1}), so pin True/False explicitly for any run
    # whose checkpoints must outlive config edits.
    scan_layers: Any = "auto"
    tie_embeddings: bool = False
    # None = auto: Pallas flash attention on TPU, materialised softmax
    # elsewhere (interpret-mode Pallas is too slow for CPU test meshes).
    use_flash: "bool | None" = None
    # Context parallelism for the attention itself (SURVEY.md §5.7 —
    # capability the reference lacks). None: XLA handles the sp axis by
    # gathering K/V (fine up to moderate T). "ring": blockwise ring
    # attention — K/V rotate the ICI ring via ppermute, O(T/n) memory per
    # device (parallel/ring.py). "ulysses": head-scatter all_to_all
    # (parallel/ulysses.py; needs n_heads % sp == 0). Both engage only
    # when the ambient mesh has an "sp" axis of size > 1.
    attention_impl: "str | None" = None
    # RMSNorm over each head's depths of q and k before the rotary
    # embedding (LFM2's q_layernorm / k_layernorm); off: the Llama form.
    qk_norm: bool = False


#: ``scan_layers="auto"`` unrolls at or below this layer count. 8 unrolled
#: tiny-config layers trace in seconds on the CPU test mesh; the 32-layer
#: production configs stay on scan (their ~3x compile cost is the real
#: trade — see the field comment above).
SCAN_LAYERS_AUTO_THRESHOLD = 8


def resolve_scan_layers(c: "LlamaConfig") -> bool:
    """The effective scan-vs-unroll choice for ``c`` (handles "auto")."""
    if c.scan_layers == "auto":
        return c.n_layers > SCAN_LAYERS_AUTO_THRESHOLD
    return bool(c.scan_layers)


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def llama_tiny(vocab: int = 256) -> LlamaConfig:
    """CPU-mesh test configuration."""
    return LlamaConfig(vocab_size=vocab, dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                       dtype=jnp.float32, remat=False, scan_layers=False)


# What each policy saves by name (``checkpoint_name``) besides what its rule
# saves: the flash-attention kernel outputs (ops/flash_attention.py
# ::_fa_fwd_impl) and an expert layer's route and grouped products
# (parallel/moe.py ROUTE, PRODUCTS).
_FLASH_OUT = ("attn_out", "attn_lse_m", "attn_lse_l")
_NAMES_SAVED = {"full": (), "dots": (),
                "dots_attn": (*_FLASH_OUT, ROUTE, PRODUCTS),
                "attn": _FLASH_OUT}

_REMAT_POLICIES = {
    "full": None,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    # "dots" + the flash-attention kernel outputs: saving (o, m, l) hands
    # the flash custom-vjp its residuals directly, so the backward runs
    # ONLY the dedicated bwd kernels — no fwd-kernel re-run inside the
    # remat block. Costs [B,T,H,D] bf16 + 2x[B,H,T] f32 per layer. An
    # expert layer's route (ints, bools and float32 [T, k], about 2 MB a
    # layer at 8,192 tokens and 10 choices) is kept too, so the backward
    # neither chooses nor places again, and so are its small plan's rows
    # and grouped products, so that it runs none of them forward again.
    "dots_attn": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(
            *_NAMES_SAVED["dots_attn"])),
    # Save ONLY the flash outputs: everything else recomputes as under
    # "full", but the backward skips the fwd-kernel re-run — the +HBM is
    # just the kernel residuals, so it composes with the HBM-bound batch
    # that made "full" win over "dots" in the first place.
    "attn": jax.checkpoint_policies.save_only_these_names(
        *_NAMES_SAVED["attn"]),
}


def remat_saves(policy_name: str, name: str) -> bool:
    """Whether the values named ``name`` outlive the forward pass of a
    block rematerialised under ``policy_name``."""
    return name in _NAMES_SAVED[policy_name]


def _remat(cls, policy_name: str, prevent_cse: bool = False):
    """``prevent_cse=False`` is right under ``nn.scan`` (the loop keeps XLA
    from merging the recomputation with the forward pass). Unrolled layers
    that count on the memory pass True: without the barrier XLA's CSE folds
    every recomputed value back into the one the forward pass made, and all
    of it stays live (latent_moe.py; 16.7 GB against 16 at its benchmark
    shape whatever the policy)."""
    if policy_name not in _REMAT_POLICIES:
        raise ValueError(f"remat_policy {policy_name!r} not in "
                         f"{sorted(_REMAT_POLICIES)}")
    return nn.remat(cls, prevent_cse=prevent_cse,
                    policy=_REMAT_POLICIES[policy_name])


def _part(init, names):
    return nn.with_logical_partitioning(init, names)


def _seq_parallel_attention(q, k, v, impl: str, scale: float):
    """Context-parallel attention inside the GSPMD step: wraps the manual
    ring/Ulysses collectives (which need a bound axis) in a ``shard_map``
    over the AMBIENT mesh, so the sp axis becomes explicit exactly for the
    attention while everything around it stays sharding-annotated.

    Returns None when the ambient mesh has no sp axis (or sp == 1) —
    caller falls through to the dense/flash path, so the same model config
    runs anywhere."""
    if impl not in ("ring", "ulysses"):
        # Validate on EVERY mesh — a typo must not silently train dense on
        # the dev box and explode on the production sp mesh.
        raise ValueError(f"attention_impl {impl!r}: use None, 'ring' or "
                         "'ulysses'")
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or "sp" not in mesh.axis_names or mesh.shape["sp"] == 1:
        return None
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel import ring_attention, ulysses_attention
    batch = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    heads = "tp" if "tp" in mesh.axis_names else None
    spec = P(batch or None, "sp", heads, None)
    if impl == "ring":
        def body(qb, kb, vb):
            return ring_attention(qb, kb, vb, "sp", causal=True, scale=scale)
    else:
        def body(qb, kb, vb):
            return ulysses_attention(qb, kb, vb, "sp", causal=True,
                                     scale=scale)
    return shard_map(body, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                     check_vma=False)(q, k, v)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", _part(nn.initializers.ones_init(),
                                          ("embed",)), (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding on [..., T, H, D]."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [.., T, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        head_dim = c.dim // c.n_heads
        B, T = x.shape[0], x.shape[1]
        # 2-D kernels (merged head×head_dim out dim): flax initialises Dense
        # kernels at their stored rank, so the logical names line up and
        # 'heads'→tp shards the merged dim — identical layout to per-head
        # sharding since head_dim is contiguous within each head.
        dense = lambda feats, names, name: nn.Dense(
            feats, use_bias=False, dtype=c.dtype, name=name,
            kernel_init=_part(nn.initializers.lecun_normal(), names))
        q = dense(c.n_heads * head_dim, ("embed", "heads"), "wq")(x)
        k = dense(c.n_kv_heads * head_dim, ("embed", "kv_heads"), "wk")(x)
        v = dense(c.n_kv_heads * head_dim, ("embed", "kv_heads"), "wv")(x)
        q = q.reshape(B, T, c.n_heads, head_dim)
        k = k.reshape(B, T, c.n_kv_heads, head_dim)
        v = v.reshape(B, T, c.n_kv_heads, head_dim)
        if c.qk_norm:
            q = RMSNorm(c.norm_eps, c.dtype, name="q_norm")(q)
            k = RMSNorm(c.norm_eps, c.dtype, name="k_norm")(k)
        q = nn_partitioning.with_sharding_constraint(
            q, ("batch", "seq", "heads", "head_dim"))
        q = rope(q, positions, c.rope_theta)
        k = rope(k, positions, c.rope_theta)
        rep = c.n_heads // c.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / head_dim ** 0.5  # python float: static for the kernel
        o = None
        if c.attention_impl is not None:
            o = _seq_parallel_attention(q, k, v, c.attention_impl, scale)
        if o is not None:
            pass
        elif _resolve_flash(c.use_flash, T):
            from ..ops.flash_attention import flash_attention
            o = flash_attention(q, k, v, causal=True, scale=scale)
        else:
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                jnp.float32) * scale
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
            # same tag as the flash path so "attn"/"dots_attn" save the
            # context on materialized-attention configs too (its einsums
            # have batch dims, so the "dots" policy recomputes them)
            from jax.ad_checkpoint import checkpoint_name
            o = checkpoint_name(
                jnp.einsum("bhqk,bkhd->bqhd", p, v), "attn_out")
        o = o.reshape(B, T, c.n_heads * head_dim)
        out = nn.Dense(
            c.dim, use_bias=False, dtype=c.dtype, name="wo",
            kernel_init=_part(nn.initializers.lecun_normal(),
                              ("heads", "embed")))(o)
        return nn_partitioning.with_sharding_constraint(
            out, ("batch", "seq", "embed"))


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        dense = lambda feats, names, name: nn.DenseGeneral(
            feats, axis=-1, use_bias=False, dtype=c.dtype, name=name,
            kernel_init=_part(nn.initializers.lecun_normal(), names))
        gate = dense(c.hidden_dim, ("embed", "mlp"), "w1")(x)
        up = dense(c.hidden_dim, ("embed", "mlp"), "w3")(x)
        h = nn.silu(gate) * up
        h = nn_partitioning.with_sharding_constraint(h, ("batch", "seq", "mlp"))
        return dense(c.dim, ("mlp", "embed"), "w2")(h)


class Block(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        x = x + Attention(c, name="attn")(
            RMSNorm(c.norm_eps, c.dtype, name="attn_norm")(x), positions)
        x = x + MLP(c, name="mlp")(
            RMSNorm(c.norm_eps, c.dtype, name="mlp_norm")(x))
        return x


class ScannedBlock(nn.Module):
    """Block with (carry, broadcast) -> (carry, None) signature for
    ``nn.scan`` over the layer axis."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        return Block(self.cfg, name="block")(x, positions), None


def decoder_trunk(mdl: nn.Module, c: LlamaConfig, tokens, block_cls,
                  scanned_cls, extra_scan_collections=()):
    """Shared decoder body (embedding → blocks → norm → lm head) used by
    Llama and Mixtral; called from inside a module's compact ``__call__`` so
    parameters stay flat under the calling module."""
    # "embed_table", not "embed": the table feeds a gather (jnp.take), and
    # an fsdp-sharded gather operand makes the SPMD partitioner replicate
    # it anyway ("involuntary full rematerialization") — a per-step
    # allgather with none of ZeRO's memory saving. Keep the table out of
    # the fsdp rule; the matmul params carry it.
    emb = mdl.param("embedding",
                    _part(nn.initializers.normal(0.02),
                          ("vocab", "embed_table")),
                    (c.vocab_size, c.dim), jnp.float32)
    x = jnp.take(emb, tokens, axis=0).astype(c.dtype)
    x = nn_partitioning.with_sharding_constraint(x, ("batch", "seq", "embed"))
    positions = jnp.arange(tokens.shape[1])[None, :]

    if resolve_scan_layers(c):
        scanned = scanned_cls
        if c.remat:
            scanned = _remat(scanned_cls, c.remat_policy)
        variable_axes = {"params": 0}
        for coll in extra_scan_collections:
            variable_axes[coll] = 0
        x, _ = nn.scan(
            scanned,
            variable_axes=variable_axes,
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=c.n_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(c, name="layers")(x, positions)
    else:
        block = _remat(block_cls, c.remat_policy) if c.remat \
            else block_cls
        for i in range(c.n_layers):
            x = block(c, name=f"block_{i}")(x, positions)
    x = RMSNorm(c.norm_eps, c.dtype, name="final_norm")(x)
    # LM head in the compute dtype with f32 ACCUMULATION (r4): an
    # f32×f32 head matmul runs at ~1/4 MXU rate and profiled as a
    # double-digit share of the Mixtral step (profile_mixtral.py);
    # bf16 inputs + preferred_element_type=f32 keep f32 logits (full
    # accumulator precision) at bf16 matmul speed.
    if c.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x.astype(c.dtype),
                            emb.astype(c.dtype),
                            preferred_element_type=jnp.float32)
    else:
        w_head = mdl.param("lm_head",
                           _part(nn.initializers.lecun_normal(),
                                 ("embed", "vocab")),
                           (c.dim, c.vocab_size), jnp.float32)
        logits = jnp.einsum("btd,dv->btv", x.astype(c.dtype),
                            w_head.astype(c.dtype),
                            preferred_element_type=jnp.float32)
    return logits


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        return decoder_trunk(self, self.cfg, tokens, Block, ScannedBlock)
