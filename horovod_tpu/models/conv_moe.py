"""Decoder of gated short-convolution layers beside grouped-query attention
layers, read from a list, with dense or sigmoid-routed expert feed-forward
parts (the LFM2-MoE layer family; the benchmark runs LFM2-8B-A1B's published
widths through it).

Named for its mechanisms, configured by the keys of the published
``config.json``:

- **Layer pattern**: layer ``l`` is ``layer_types[l]`` (``conv`` or
  ``full_attention``); its feed-forward part is a dense SwiGLU of
  ``intermediate_size`` below ``num_dense_layers`` and the expert layer
  above; unrolled. ``y = x + Mix(norm(x)); z = y + FFN(norm(y))``.
- **Gated short convolution** (``hvd_short_conv``): ``[B | C | u] = h
  W_in`` (``3 * hidden_size`` wide, in that order), ``v = B * u``, a
  depthwise causal convolution of ``conv_L_cache`` taps a channel over ``v``
  with zeros before the first position, ``(C * z) W_out``. The gates and the
  taps (``hvd_conv_mix``) are shifted products and sums in the compute type,
  which XLA fuses; nothing crosses a sequence's start.
- **Attention** (``hvd_attn_full``): ``models/llama.py Attention`` with
  RMSNorm over each head's depths of q and k before the rotary embedding
  (``qk_norm``), query head ``h`` reading KV head ``h // (H / KV)``.
- **Expert layer**: ``models/latent_moe.py ExpertLayer`` with no shared
  expert (``parallel/moe.py dropless_share``: float32 sigmoid scores over
  ALL ``num_experts``, the ``num_experts_per_tok`` largest of score +
  selection bias chosen, gates the chosen scores renormalised and scaled by
  ``routed_scaling_factor``, no capacity, the experts ``[first_expert,
  first_expert + experts_held)`` computed). With ``use_expert_bias`` the
  selection bias moves by ``bias_update_rate`` a step against the expert's
  load; ``bias``, ``counts`` and ``rows`` ride ``TrainState.batch_stats``.
- **Head**: a final RMSNorm (``embedding_norm``), then the embedding table
  as the output projection (tied).

A rank's share of a layer is given beside the published counts:
``experts_held`` experts from ``first_expert``, and whatever slice of the
vocabulary ``vocab_size`` says. What the absent experts would add to the
layer's sum is left out.

``__call__(tokens [B, S], train)`` returns float32 logits ``[B, S, V]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..core import telemetry as _telemetry
from .latent_moe import ExpertLayer, LatentMoEConfig
from .llama import MLP, Attention, LlamaConfig, RMSNorm, _part, _remat

CONV, FULL = "conv", "full_attention"
LFM2_8B_A1B_LAYERS = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 4 \
    + (FULL, CONV, CONV, FULL, CONV, CONV)


@dataclasses.dataclass(frozen=True)
class ConvMoEConfig:
    # keys of the published config.json, under their names
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: Tuple[str, ...] = LFM2_8B_A1B_LAYERS
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_experts: int = 32               # the router's width
    num_experts_per_tok: int = 4
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    # this rank's share of each expert layer (None: every expert)
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
        if self.conv_bias or not self.norm_topk_prob:
            raise ValueError("conv_bias and an unnormalised top-k are not "
                             "implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("the heads do not divide over the KV heads")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def attention(self) -> LlamaConfig:
        """An attention layer, in ``models/llama.py``'s names."""
        return LlamaConfig(
            dim=self.hidden_size, n_heads=self.num_attention_heads,
            n_kv_heads=self.num_key_value_heads, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            use_flash=self.use_flash, qk_norm=True)

    @property
    def dense_mlp(self) -> LlamaConfig:
        """A dense feed-forward part, in ``models/llama.py``'s names."""
        return LlamaConfig(dim=self.hidden_size,
                           hidden_dim=self.intermediate_size,
                           dtype=self.dtype)

    @property
    def expert_layer(self) -> LatentMoEConfig:
        """An expert layer, in ``models/latent_moe.py``'s names: no shared
        expert, and the rematerialisation of the block it lies in."""
        return LatentMoEConfig(
            hidden_size=self.hidden_size,
            moe_intermediate_size=self.moe_intermediate_size,
            n_routed_experts=self.num_experts, n_shared_experts=0,
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=self.routed_scaling_factor,
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=self.bias_update_rate
            if self.use_expert_bias else 0.0,
            dtype=self.dtype, remat=self.remat,
            remat_policy=self.remat_policy, axis_name=self.axis_name)


def conv_moe_tiny(vocab: int = 128, **over) -> ConvMoEConfig:
    """CPU-mesh test configuration: a dense conv layer, then attention and
    conv expert layers; 4 query heads over 2 KV heads, 8 experts of which 3
    are held and 2 chosen."""
    return ConvMoEConfig(**{**dict(
        vocab_size=vocab, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_attention_heads=4,
        num_key_value_heads=2, layer_types=(CONV, FULL, CONV, CONV),
        num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
        experts_held=3, first_expert=2, rope_theta=10000.0,
        dtype=jnp.float32, remat=False), **over})


def causal_taps(v, taps):
    """The depthwise causal convolution of ``v [..., T, D]`` with ``taps
    [L, D]``: ``z_t = sum_j taps[j] * v_{t - (L - 1) + j}``, zero before
    position 0 (PyTorch's ``Conv1d(groups=D, padding=L - 1)`` cut to
    ``T``). Shifted products and sums, in the input's type."""
    T, L = v.shape[-2], taps.shape[0]
    padded = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(L - 1, 0), (0, 0)])
    z = taps[0] * padded[..., :T, :]
    for j in range(1, L):
        z = z + taps[j] * padded[..., j:j + T, :]
    return z


def gated_short_conv(bcu, taps):
    """``C * conv(B * u)`` of an input projection ``bcu [..., T, 3D]``
    (``B``, ``C``, ``u`` in that order along the features) with depthwise
    causal ``taps [L, D]`` (:func:`causal_taps` over ``v = B * u``)."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    return c * causal_taps(b * u, taps)


def _dense(c, features, names, name):
    return nn.Dense(features, use_bias=False, dtype=c.dtype, name=name,
                    kernel_init=_part(nn.initializers.lecun_normal(), names))


class ShortConv(nn.Module):
    """``(C * conv(B * u)) W_out`` with ``[B | C | u] = x W_in``."""

    cfg: ConvMoEConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        D, L = c.hidden_size, c.conv_L_cache
        _telemetry.inc("hvd_conv_layers_total", kernel=L, width=D)
        with jax.named_scope("hvd_short_conv"):
            bcu = _dense(c, 3 * D, ("embed", "mlp"), "in_proj")(x)
            # a tap a channel: fan-in L, as a depthwise Conv1d's
            taps = self.param("taps", _part(nn.initializers.lecun_normal(),
                                            (None, "mlp")), (L, D))
            with jax.named_scope("hvd_conv_mix"):
                z = gated_short_conv(bcu, taps.astype(c.dtype))
            return _dense(c, D, ("mlp", "embed"), "out_proj")(z)


class ConvMoEBlock(nn.Module):
    """``y = x + Mix(norm(x)); z = y + FFN(norm(y))``, the mixer by the
    layer's kind and the feed-forward part by its index."""

    cfg: ConvMoEConfig
    layer: int = 0
    train: bool = False     # an attribute: static under ``nn.remat``

    @nn.compact
    def __call__(self, x, positions):
        c, kind = self.cfg, self.cfg.layer_types[self.layer]
        norm = lambda name: RMSNorm(c.norm_eps, c.dtype, name=name)
        h = norm("operator_norm")(x)
        if kind == CONV:
            x = x + ShortConv(c, name="conv")(h)
        elif kind == FULL:
            a = c.attention
            _telemetry.inc("hvd_attn_layers_total", kind=kind,
                           heads=a.n_heads, kv_heads=a.n_kv_heads)
            with jax.named_scope("hvd_attn_full"):
                x = x + Attention(a, name="self_attn")(h, positions)
        else:
            raise ValueError(f"layer_types[{self.layer}] = {kind!r}")
        h = norm("ffn_norm")(x)
        if self.layer < c.num_dense_layers:
            return x + MLP(c.dense_mlp, name="feed_forward")(h)
        return x + ExpertLayer(c.expert_layer, self.train,
                               name="feed_forward")(h)


class ConvMoE(nn.Module):
    cfg: ConvMoEConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        emb = self.param("embedding", _part(nn.initializers.normal(0.02),
                                            ("vocab", "embed_table")),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        # unrolled layers: the recomputation needs its CSE barrier
        block = _remat(ConvMoEBlock, c.remat_policy, prevent_cse=True) \
            if c.remat else ConvMoEBlock
        positions = jnp.arange(tokens.shape[1])[None, :]
        x = jnp.take(emb, tokens, axis=0).astype(c.dtype)
        for i in range(c.num_hidden_layers):
            x = block(c, layer=i, train=train, name=f"layer_{i}")(
                x, positions)
        x = RMSNorm(c.norm_eps, c.dtype, name="embedding_norm")(x)
        # the table is the head: operands in the compute type, float32
        # accumulation and logits (as ``models/llama.py``'s tied head)
        return jnp.einsum("bsd,vd->bsv", x, emb.astype(x.dtype),
                          preferred_element_type=jnp.float32)
