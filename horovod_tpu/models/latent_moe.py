"""Latent-attention decoder with sigmoid-routed experts, a shared expert and
a multi-token-prediction module (the DeepSeek-V3 layer family; the benchmark
runs JoyAI-LLM-Flash's published widths through it).

Named for its mechanisms, configured by the keys of the published
``config.json``:

- **Latent attention** (every layer): queries through a 1536-wide latent,
  keys and values through a 512-wide one plus ONE rotary key vector a
  position shared by all heads; query/key depth ``qk_nope_head_dim +
  qk_rope_head_dim``, value depth ``v_head_dim``. In training the keys and
  values are expanded per head and go through the flash kernels, which take
  a value depth of their own (``ops/flash_attention.py``). Off by default
  and used by ``models/delta_moe.py``: a direct query projection
  (``q_lora_rank`` None), an RMSNorm a head over q's and k's depths before
  the rotary embedding (``use_qk_norm``), and a sigmoid gate a head and
  position on the output (``head_gate``, ``window_moe.head_gate``).
- **Layer pattern**: ``first_k_dense_replace`` dense SwiGLU layers, then
  expert layers, unrolled.
- **Expert layer**: ``parallel/moe.py dropless_share`` (float32 sigmoid
  scores over ALL ``n_routed_experts``, top-k of score + bias within the
  ``topk_group`` best of ``n_group`` groups, gates
  renormalised and scaled, no capacity) for the experts this rank holds,
  ``[first_expert, first_expert + experts_held)``, plus the shared expert on
  every token. The selection bias and the per-expert token counts are model
  state that no gradient trains: they live in the ``batch_stats`` collection
  and ride ``TrainState.batch_stats`` like BatchNorm's statistics. Counts
  are summed over ``axis_name`` inside the model before the bias moves, as
  sync-BatchNorm reduces inside the model.
- **Multi-token prediction**: one module that takes the trunk's last hidden
  state (before the final norm) with the NEXT token's embedding, runs one
  expert block and its own final norm, and shares the embedding and the
  head; it predicts token ``t + 2``.

``__call__(tokens [B, S + n], train)`` with ``n =
num_nextn_predict_layers`` returns ``(trunk logits [B, S, V], module logits
[B, S, V])`` (the trunk's alone when ``n == 0``): the trunk reads the first
``S`` ids, the module the embeddings of ids ``1..S``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..collectives.ops import effective_axis_size
from ..core import telemetry as _telemetry
from ..parallel.moe import PRODUCTS, ROUTE, balance_bias, dropless_share
from ._flash import resolve_flash as _resolve_flash
from .llama import RMSNorm, _part, _remat, remat_saves


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    # keys of the published config.json, under their names
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = 1536   # None: one direct query projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 256         # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    # an RMSNorm a head over q's and k's depths before the rotary
    # embedding, and a sigmoid gate a head and position on the output
    use_qk_norm: bool = False
    head_gate: bool = False
    # this rank's share of each expert layer (None: every expert)
    experts_held: Optional[int] = None
    first_expert: int = 0
    # not in config.json (DeepSeek-V3, arXiv:2412.19437 section 4.2)
    bias_update_rate: float = 0.001
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    use_flash: "bool | None" = None
    # rank axis over which the experts' token counts are summed
    axis_name: Optional[str] = None

    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held


def latent_moe_tiny(vocab: int = 128, **over) -> LatentMoEConfig:
    """CPU-mesh test configuration: 8 experts of which 2 are held."""
    return LatentMoEConfig(**{**dict(
        vocab_size=vocab, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
        rope_theta=10000.0, n_routed_experts=8, num_experts_per_tok=3,
        experts_held=2, first_expert=2, dtype=jnp.float32, remat=False),
        **over})


def rope_interleaved(x, positions, theta: float):
    """Rotary embedding on [..., T, H, D] that rotates ADJACENT pairs
    ``(2i, 2i + 1)`` (``rope_interleave: true``)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _dense(c, features, names, name):
    return nn.Dense(features, use_bias=False, dtype=c.dtype, name=name,
                    kernel_init=_part(nn.initializers.lecun_normal(), names))


class LatentAttention(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        B, T, _ = x.shape
        H, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        with jax.named_scope("hvd_mla"):
            if c.q_lora_rank is None:
                q = _dense(c, H * (dn + dr), ("embed", "heads"), "wq")(x)
            else:
                cq = norm("q_norm")(_dense(c, c.q_lora_rank, ("embed", None),
                                           "wq_a")(x))
                q = _dense(c, H * (dn + dr), (None, "heads"), "wq_b")(cq)
            q = q.reshape(B, T, H, dn + dr)
            kva = _dense(c, c.kv_lora_rank + dr, ("embed", None), "wkv_a")(x)
            ckv = norm("kv_norm")(kva[..., :c.kv_lora_rank])
            k_rope = kva[..., c.kv_lora_rank:].reshape(B, T, 1, dr)
            kv = _dense(c, H * (dn + dv), (None, "heads"), "wkv_b")(ckv)
            kv = kv.reshape(B, T, H, dn + dv)
            k_nope = kv[..., :dn]
            if c.use_qk_norm:
                q = norm("q_head_norm")(q)
                k = norm("k_head_norm")(jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))], -1))
                k_nope, k_rope = k[..., :dn], k[..., dn:]
            q = jnp.concatenate([
                q[..., :dn],
                rope_interleaved(q[..., dn:], positions, c.rope_theta)], -1)
            k = jnp.concatenate([
                k_nope,
                jnp.broadcast_to(
                    rope_interleaved(k_rope, positions, c.rope_theta),
                    (B, T, H, dr))], -1)
            v = kv[..., dn:]
            scale = 1.0 / (dn + dr) ** 0.5
            if _resolve_flash(c.use_flash, T):
                from ..ops.flash_attention import flash_attention
                o = flash_attention(q, k, v, causal=True, scale=scale)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                    jnp.float32) * scale
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None],
                              s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                from jax.ad_checkpoint import checkpoint_name
                o = checkpoint_name(
                    jnp.einsum("bhqk,bkhd->bqhd", p, v), "attn_out")
            if c.head_gate:
                from .window_moe import head_gate
                with jax.named_scope("hvd_head_gate"):
                    g = head_gate(_dense(c, H, ("embed", "heads"), "wg")(x))
                    o = o * g[..., None].astype(o.dtype)
            return _dense(c, c.hidden_size, ("heads", "embed"), "wo")(
                o.reshape(B, T, H * dv))


class SwiGLU(nn.Module):
    cfg: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = nn.silu(_dense(c, self.width, ("embed", "mlp"), "w1")(x)) \
            * _dense(c, self.width, ("embed", "mlp"), "w3")(x)
        return _dense(c, c.hidden_size, ("mlp", "embed"), "w2")(h)


class ExpertLayer(nn.Module):
    """This rank's share of the routed experts plus the shared expert
    (none, and no ``shared`` parameters, at ``n_shared_experts == 0``)."""

    cfg: LatentMoEConfig
    train: bool = False

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, T, D = x.shape
        E, held, M = c.n_routed_experts, c.held, c.moe_intermediate_size
        tokens = x.reshape(B * T, D)
        logits = nn.Dense(
            E, use_bias=False, dtype=jnp.float32, name="router",
            kernel_init=_part(nn.initializers.lecun_normal(),
                              ("embed", None)))(tokens.astype(jnp.float32))
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w1 = self.param("w1", _part(init, ("experts", "embed", "mlp")),
                        (held, D, M))
        w3 = self.param("w3", _part(init, ("experts", "embed", "mlp")),
                        (held, D, M))
        w2 = self.param("w2", _part(init, ("experts", "mlp", "embed")),
                        (held, M, D))
        bias = self.variable("batch_stats", "bias", jnp.zeros, (E,))
        seen = self.variable("batch_stats", "counts", jnp.zeros, (E,))
        rows = self.variable("batch_stats", "rows", jnp.zeros, (held,))
        # whether the backward gets what the forward made or makes it
        # again: the block's remat policy
        saved = lambda name: not c.remat or remat_saves(c.remat_policy, name)
        y, counts, filled = dropless_share(
            tokens, logits, bias.value, w1.astype(c.dtype),
            w3.astype(c.dtype), w2.astype(c.dtype), first=c.first_expert,
            top_k=c.num_experts_per_tok, scaling=c.routed_scaling_factor,
            keep_products=saved(PRODUCTS), n_group=c.n_group,
            topk_group=c.topk_group)
        # trace-time, one a traced layer
        _telemetry.inc("hvd_moe_route_total", placement="count",
                       saved=str(saved(ROUTE)).lower())
        if self.train and self.is_mutable_collection("batch_stats"):
            if c.axis_name is not None \
                    and effective_axis_size(c.axis_name) != 1:
                counts, filled = jax.lax.psum((counts, filled), c.axis_name)
            bias.value = balance_bias(bias.value, counts,
                                      c.bias_update_rate)
            seen.value, rows.value = counts, filled
        if not c.n_shared_experts:
            return y.astype(c.dtype).reshape(B, T, D)
        with jax.named_scope("hvd_moe_shared"):
            shared = SwiGLU(c, c.n_shared_experts * M, name="shared")(x)
        return y.astype(c.dtype).reshape(B, T, D) + shared


class LatentBlock(nn.Module):
    """``h = x + Attn(norm(x)); y = h + FFN(norm(h))``; the FFN is dense
    SwiGLU or the expert layer."""

    cfg: LatentMoEConfig
    dense: bool = False
    train: bool = False     # an attribute: static under ``nn.remat``

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        x = x + LatentAttention(c, name="attn")(norm("attn_norm")(x),
                                                positions)
        h = norm("mlp_norm")(x)
        if self.dense:
            return x + SwiGLU(c, c.intermediate_size, name="mlp")(h)
        return x + ExpertLayer(c, self.train, name="moe")(h)


class LatentMoE(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        n_next = c.num_nextn_predict_layers
        if n_next not in (0, 1):
            raise ValueError("one prediction module at most")
        S = tokens.shape[1] - n_next
        emb = self.param("embedding", _part(nn.initializers.normal(0.02),
                                            ("vocab", "embed_table")),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        w_head = self.param("lm_head", _part(nn.initializers.lecun_normal(),
                                             ("embed", "vocab")),
                            (c.hidden_size, c.vocab_size), jnp.float32)
        # unrolled layers: the recomputation needs its CSE barrier
        block = _remat(LatentBlock, c.remat_policy, prevent_cse=True) \
            if c.remat else LatentBlock
        positions = jnp.arange(S)[None, :]
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)

        def head(h):        # float32 operands, as models/bert.py's head
            return jnp.einsum("btd,dv->btv", h.astype(jnp.float32), w_head)

        embedded = jnp.take(emb, tokens, axis=0).astype(c.dtype)
        x = embedded[:, :S]
        for i in range(c.num_hidden_layers):
            x = block(c, dense=i < c.first_k_dense_replace, train=train,
                      name=f"layer_{i}")(x, positions)
        logits = head(norm("final_norm")(x))
        if not n_next:
            return logits
        with jax.named_scope("hvd_mtp"):
            u = jnp.concatenate([norm("mtp_embed_norm")(embedded[:, 1:]),
                                 norm("mtp_hidden_norm")(x)], axis=-1)
            u = _dense(c, c.hidden_size, (None, "embed"), "mtp_proj")(u)
            u = block(c, train=train, name="mtp_block")(u, positions)
            return logits, head(norm("mtp_final_norm")(u))


def next_tokens_loss(mtp_weight: float = 0.3):
    """``loss_fn(outputs, labels)`` for ``make_train_step``: labels are the
    ids one to the right of the model's input, ``[B, S + n]``. Cross
    entropy of the trunk on token ``t + 1`` plus ``mtp_weight`` times the
    module's on ``t + 2``, each a mean over its positions, in float32."""
    def ce(logits, targets):
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
        return (lse - tgt).mean()

    def loss_fn(outputs, labels):
        if not isinstance(outputs, tuple):
            return ce(outputs, labels)
        trunk, module = outputs
        return ce(trunk, labels[:, :-1]) \
            + mtp_weight * ce(module, labels[:, 1:])
    return loss_fn
