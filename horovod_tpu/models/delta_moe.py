"""Decoder of Kimi delta attention layers beside latent attention layers, by
a fixed period, with dense or group-routed expert feed-forward parts (the
``bailing_hybrid`` layer family; the benchmark runs Ling-3.0-flash's
published widths through it).

Named for its mechanisms, configured by the keys of the published
``config.json``:

- **Layer pattern**: layer ``l`` (its published index) is latent attention
  when ``(l + 1) % layer_group_size == 0`` and Kimi delta attention
  otherwise; the first ``first_k_dense_replace`` layers held have a dense
  SwiGLU of ``intermediate_size``, the others the expert layer; unrolled.
  ``h = x + Mix(norm(x)); y = h + FFN(norm(h))``.
- **Kimi delta attention** (``hvd_kda``; Kimi Linear, arXiv:2510.26692),
  ``H`` heads of ``head_dim`` ``d``, each with its own k and v: ``q, k, v =
  SiLU(causal depthwise conv of short_conv_kernel_size taps over x [W_q |
  W_k | W_v])``; q and k L2-normalised a head, q scaled by ``d ** -0.5``; a
  log-decay a channel ``g = kda_lower_bound * sigmoid(exp(A_log) * (x W_f +
  dt_bias))``, a write strength a head ``beta = sigmoid(x W_b)``; the gated
  delta rule over them (``ops/delta_rule.py``, ``hvd_kda_scan``); ``y =
  (RMSNorm_head(o) * sigmoid(x W_g)) W_o``.
- **Latent attention** (``hvd_mla``): ``models/latent_moe.py
  LatentAttention`` with a direct query projection, an RMSNorm a head over
  q's and k's depths before the rotary embedding and a sigmoid gate a head.
- **Expert layer**: ``models/latent_moe.py ExpertLayer`` with the shared
  expert (``parallel/moe.py dropless_share``: float32 sigmoid scores over
  ALL ``num_experts``, the ``num_experts_per_tok`` largest of score +
  selection bias among the experts of the ``topk_group`` best of
  ``n_group`` groups, gates renormalised and scaled, no capacity, the
  experts ``[first_expert, first_expert + experts_held)`` computed).
- **Head**: a final RMSNorm, then an untied float32 output projection.

A rank's share is given beside the published counts: the published index
of each layer held (``layer_indices``), ``experts_held`` experts from
``first_expert``, and whatever slice of the vocabulary ``vocab_size`` says.

``__call__(tokens [B, S], train)`` returns float32 logits ``[B, S, V]``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..core import telemetry as _telemetry
from ..ops.delta_rule import CHUNK, LOWER, delta_rule
from .conv_moe import causal_taps
from .latent_moe import ExpertLayer, LatentAttention, LatentMoEConfig, SwiGLU
from .llama import RMSNorm, _part, _remat

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class DeltaMoEConfig:
    # keys of the published config.json, under their names
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128
    num_kv_heads_for_linear_attn: int = 0
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    rms_norm_eps: float = 1e-6
    use_qk_norm: bool = True
    gated_attention_proj_granularity_type: Optional[str] = "head_wise"
    num_experts: int = 512              # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    moe_router_enable_expert_bias: bool = True
    # this rank's share: the published index of each layer held (None:
    # every layer) and the experts of each expert layer (None: every one)
    layer_indices: Optional[Tuple[int, ...]] = None
    experts_held: Optional[int] = None
    first_expert: int = 0
    # not in config.json: the selection bias's step (DeepSeek-V3,
    # arXiv:2412.19437 section 2.1.2)
    bias_update_rate: float = 0.001
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots_attn"
    use_flash: "bool | None" = None
    # rank axis over which the experts' token counts are summed
    axis_name: Optional[str] = None

    def __post_init__(self):
        if self.num_kv_heads_for_linear_attn or not self.norm_topk_prob:
            raise ValueError("shared linear-attention KV heads and an "
                             "unnormalised top-k are not implemented")
        if self.kda_lower_bound < LOWER:
            raise ValueError(f"a log-decay below {LOWER} a step is outside "
                             "what ops/delta_rule.py admits")
        if self.gated_attention_proj_granularity_type not in (None,
                                                              "head_wise"):
            raise ValueError("only a head-wise attention gate is implemented")
        if self.moe_shared_expert_intermediate_size \
                != self.num_shared_experts * self.moe_intermediate_size:
            raise ValueError("the shared expert is num_shared_experts "
                             "experts wide")
        if self.layer_indices is not None \
                and len(self.layer_indices) != self.num_hidden_layers:
            raise ValueError("layer_indices is not num_hidden_layers long")

    def layer_kind(self, layer: int) -> str:
        """``mla`` or ``kda`` of the ``layer``-th layer held."""
        index = layer if self.layer_indices is None \
            else self.layer_indices[layer]
        return MLA if (index + 1) % self.layer_group_size == 0 else KDA

    @property
    def attention(self) -> LatentMoEConfig:
        """A latent-attention layer, in ``models/latent_moe.py``'s names."""
        return LatentMoEConfig(
            hidden_size=self.hidden_size,
            num_attention_heads=self.num_attention_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            rms_norm_eps=self.rms_norm_eps, use_qk_norm=self.use_qk_norm,
            head_gate=self.gated_attention_proj_granularity_type
            == "head_wise", dtype=self.dtype, use_flash=self.use_flash)

    @property
    def expert_layer(self) -> LatentMoEConfig:
        """An expert layer (and a dense SwiGLU's compute type), in
        ``models/latent_moe.py``'s names, with the rematerialisation of
        the block it lies in."""
        return LatentMoEConfig(
            hidden_size=self.hidden_size,
            moe_intermediate_size=self.moe_intermediate_size,
            n_routed_experts=self.num_experts,
            n_shared_experts=self.num_shared_experts,
            num_experts_per_tok=self.num_experts_per_tok,
            n_group=self.n_group, topk_group=self.topk_group,
            routed_scaling_factor=self.routed_scaling_factor,
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=self.bias_update_rate
            if self.moe_router_enable_expert_bias else 0.0,
            dtype=self.dtype, remat=self.remat,
            remat_policy=self.remat_policy, axis_name=self.axis_name)


def delta_moe_tiny(vocab: int = 128, **over) -> DeltaMoEConfig:
    """CPU-mesh test configuration: a dense delta layer, then delta and
    latent expert layers (a period of 3); 2 heads of 8, 16 experts in 4
    groups of which 2 are kept, 3 held and 4 chosen."""
    return DeltaMoEConfig(**{**dict(
        vocab_size=vocab, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
        num_hidden_layers=4, first_k_dense_replace=1, layer_group_size=3,
        num_attention_heads=2, head_dim=8, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
        rope_theta=10000.0, num_experts=16, num_experts_per_tok=4,
        n_group=4, topk_group=2, experts_held=3, first_expert=2,
        dtype=jnp.float32, remat=False), **over})


def _dense(c, features, names, name):
    return nn.Dense(features, use_bias=False, dtype=c.dtype, name=name,
                    kernel_init=_part(nn.initializers.lecun_normal(), names))


def _l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of steps drawn log-uniform in [0.001, 0.1]
    (flash-linear-attention's ``KimiDeltaAttention``): slow decays at
    initialisation."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (
        math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class KimiDeltaAttention(nn.Module):
    cfg: DeltaMoEConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, T, _ = x.shape
        H, d, L = c.num_attention_heads, c.head_dim, c.short_conv_kernel_size
        # trace-time, one a traced layer
        _telemetry.inc("hvd_kda_layers_total", heads=H, head_dim=d,
                       chunk=CHUNK)
        with jax.named_scope("hvd_kda"):
            qkv = _dense(c, 3 * H * d, ("embed", "heads"), "qkv_proj")(x)
            # a tap a channel: fan-in L, as a depthwise Conv1d's
            taps = self.param("conv", _part(nn.initializers.lecun_normal(),
                                            (None, "heads")), (L, 3 * H * d))
            qkv = nn.silu(causal_taps(qkv, taps.astype(c.dtype)))
            q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, d), 3, axis=2)
            q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
            a_log = self.param("A_log", _a_log_init, (H,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (H * d,))
            f = _dense(c, H * d, ("embed", "heads"), "f_proj")(x)
            g = c.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log)[:, None]
                * (f.astype(jnp.float32) + dt_bias).reshape(B, T, H, d))
            beta = jax.nn.sigmoid(_dense(c, H, ("embed", "heads"), "b_proj")(
                x).astype(jnp.float32))
            o = delta_rule(q, k, v, g, beta)
            o = RMSNorm(c.rms_norm_eps, jnp.float32, name="o_norm")(o)
            gate = _dense(c, H * d, ("embed", "heads"), "g_proj")(x)
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).reshape(
                B, T, H, d)
            return _dense(c, c.hidden_size, ("heads", "embed"), "o_proj")(
                o.astype(c.dtype).reshape(B, T, H * d))


class DeltaBlock(nn.Module):
    """``h = x + Mix(norm(x)); y = h + FFN(norm(h))``, the mixer by the
    layer's place in the period and the feed-forward part by its index."""

    cfg: DeltaMoEConfig
    layer: int = 0
    train: bool = False     # an attribute: static under ``nn.remat``

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        h = norm("attn_norm")(x)
        if c.layer_kind(self.layer) == MLA:
            x = x + LatentAttention(c.attention, name="attn")(h, positions)
        else:
            x = x + KimiDeltaAttention(c, name="attn")(h)
        h = norm("mlp_norm")(x)
        if self.layer < c.first_k_dense_replace:
            return x + SwiGLU(c.expert_layer, c.intermediate_size,
                              name="mlp")(h)
        return x + ExpertLayer(c.expert_layer, self.train, name="moe")(h)


class DeltaMoE(nn.Module):
    cfg: DeltaMoEConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        emb = self.param("embedding", _part(nn.initializers.normal(0.02),
                                            ("vocab", "embed_table")),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        w_head = self.param("lm_head", _part(nn.initializers.lecun_normal(),
                                             ("embed", "vocab")),
                            (c.hidden_size, c.vocab_size), jnp.float32)
        # unrolled layers: the recomputation needs its CSE barrier
        block = _remat(DeltaBlock, c.remat_policy, prevent_cse=True) \
            if c.remat else DeltaBlock
        positions = jnp.arange(tokens.shape[1])[None, :]
        x = jnp.take(emb, tokens, axis=0).astype(c.dtype)
        for i in range(c.num_hidden_layers):
            x = block(c, layer=i, train=train, name=f"layer_{i}")(
                x, positions)
        x = RMSNorm(c.rms_norm_eps, c.dtype, name="final_norm")(x)
        # float32 operands, as models/latent_moe.py's head
        return jnp.einsum("btd,dv->btv", x.astype(jnp.float32), w_head)
