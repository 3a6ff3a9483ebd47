"""Decoder whose layers differ by a pattern read from lists: window and
full attention layers of unlike head counts over one count of KV heads, a
sigmoid gate a head on the attention output, a rotary scheme a layer kind,
and dense or sigmoid-routed expert feed-forward parts (the benchmark runs
Laguna-S-2.1's published widths through it).

Named for its mechanisms, configured by the keys of the published
``config.json``:

- **Layer pattern**: layer ``l`` is ``layer_types[l]`` (``full_attention``
  or ``sliding_attention``) with ``num_attention_heads_per_layer[l]`` query
  heads over ``num_key_value_heads`` KV heads, and its feed-forward part is
  ``mlp_layer_types[l]`` (``dense`` or ``sparse``); unrolled.
- **Attention** (``hvd_attn_full`` / ``hvd_attn_window``): query head ``h``
  reads KV head ``h // (H / KV)`` (grouped by ``jnp.repeat`` before the
  kernel call, as ``models/llama.py``); a full layer sees every key ``j <=
  i``, a sliding one ``i - sliding_window < j <= i`` through
  ``ops/flash_attention.py``'s ``window``.
- **Rotary, a scheme a layer kind** (``rope_parameters``): a sliding layer
  rotates the whole head at its own base; a full layer rotates the first
  ``partial_rotary_factor`` of the head's depths by YaRN's frequencies
  (:func:`yarn_inv_freq`) with cos and sin scaled by ``attention_factor``,
  and passes the other depths unrotated (:func:`rope_partial`).
- **A gate a head** (``hvd_head_gate``): ``g = sigmoid(x Wg)`` ``[T, H]``
  from the layer's normed input, float32, times each head's output before
  the output product (arXiv:2505.06708).
- **Expert layer**: ``models/latent_moe.py ExpertLayer`` as it is
  (``parallel/moe.py dropless_share``: float32 sigmoid scores over ALL
  ``num_experts``, the ``num_experts_per_tok`` largest chosen, gates
  renormalised and scaled by ``moe_routed_scaling_factor``, no capacity,
  the experts ``[first_expert, first_expert + experts_held)`` computed) plus
  the shared expert; its selection bias is zero and its update rate 0, so it
  never moves; ``counts`` and ``rows`` ride ``TrainState.batch_stats``.

A rank's share of a layer is given beside the published counts:
``kv_heads_held`` KV heads with their query heads (``H / KV`` a KV head, as
published), ``experts_held`` experts from ``first_expert``, and whatever
slice of the vocabulary ``vocab_size`` says. What the absent heads and
experts would add to the layer's sums is left out.

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
from ._flash import resolve_flash as _resolve_flash
from .latent_moe import ExpertLayer, LatentMoEConfig
from .llama import MLP, LlamaConfig, RMSNorm, _part, _remat

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class RopeScheme:
    """One entry of the published ``rope_parameters``. ``factor`` None:
    plain frequencies (``rope_type`` "default"); else YaRN's."""
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    factor: Optional[float] = None
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_published(cls, entry: dict) -> "RopeScheme":
        keep = ("rope_theta", "partial_rotary_factor")
        if entry.get("rope_type", "default") == "yarn":
            keep += ("factor", "original_max_position_embeddings",
                     "beta_fast", "beta_slow", "attention_factor")
        return cls(**{k: entry[k] for k in keep if k in entry})


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    # keys of the published config.json, under their names
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    head_dim: int = 128
    num_key_value_heads: int = 8
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING)
    mlp_layer_types: Tuple[str, ...] = ("dense", "sparse", "sparse",
                                        "sparse")
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72)
    sliding_window: int = 512
    rope_full: RopeScheme = RopeScheme(
        500000.0, 0.5, 128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    rope_sliding: RopeScheme = RopeScheme(10000.0)
    rms_norm_eps: float = 1e-6
    num_experts: int = 256              # the router's width
    num_experts_per_tok: int = 10
    moe_routed_scaling_factor: float = 2.5
    # this rank's share of each layer (None: all of it)
    kv_heads_held: Optional[int] = None
    experts_held: Optional[int] = None
    first_expert: int = 0
    # not in config.json
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    use_flash: "bool | None" = None
    # rank axis over which the experts' token counts are summed
    axis_name: Optional[str] = None

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads if self.kv_heads_held is None \
            else self.kv_heads_held

    def heads(self, layer: int) -> int:
        """Query heads of ``layer`` held here: the published count a KV
        head, times the KV heads held."""
        return self.num_attention_heads_per_layer[layer] \
            // self.num_key_value_heads * self.kv_heads

    @property
    def dense_mlp(self) -> LlamaConfig:
        """A dense feed-forward part, in ``models/llama.py``'s names."""
        return LlamaConfig(dim=self.hidden_size,
                           hidden_dim=self.intermediate_size,
                           dtype=self.dtype)

    @property
    def expert_layer(self) -> LatentMoEConfig:
        """An expert layer, in ``models/latent_moe.py``'s names: no
        selection bias (its rate is 0, so the zero it starts from stays),
        and the rematerialisation of the block it lies in."""
        return LatentMoEConfig(
            hidden_size=self.hidden_size,
            moe_intermediate_size=self.moe_intermediate_size,
            n_routed_experts=self.num_experts,
            n_shared_experts=self.shared_expert_intermediate_size
            // self.moe_intermediate_size,
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=self.moe_routed_scaling_factor,
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=0.0, dtype=self.dtype, remat=self.remat,
            remat_policy=self.remat_policy, axis_name=self.axis_name)


def window_moe_tiny(vocab: int = 128, **over) -> WindowMoEConfig:
    """CPU-mesh test configuration: a dense full layer, two sliding expert
    layers and a full expert layer; 2 and 3 query heads a KV head over 4 KV
    heads, 8 experts of which 3 are chosen, a window of 5."""
    return WindowMoEConfig(**{**dict(
        vocab_size=vocab, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        head_dim=16, num_key_value_heads=4,
        layer_types=(FULL, SLIDING, SLIDING, FULL),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
        num_attention_heads_per_layer=(8, 12, 12, 8), sliding_window=5,
        rope_full=RopeScheme(500000.0, 0.5, 4.0, 8, 32.0, 1.0, 1.2),
        num_experts=8, num_experts_per_tok=3, dtype=jnp.float32,
        remat=False), **over})


def yarn_inv_freq(scheme: RopeScheme, head_dim: int):
    """The rotary frequencies of ``scheme`` for a head ``head_dim`` deep, as
    ``transformers`` computes them, in Python floats: ``dim = head_dim *
    partial_rotary_factor`` depths rotate, ``dim / 2`` frequencies. Plain:
    ``theta ** (-2i / dim)``. YaRN (arXiv:2309.00071): frequency ``i`` is the
    plain one where the pair turns more than ``beta_fast`` times over the
    original positions, the plain one over ``factor`` where it turns fewer
    than ``beta_slow`` times, and a linear blend between the two dimensions
    where that happens (floor and ceiling taken)."""
    dim = int(head_dim * scheme.partial_rotary_factor)
    plain = [scheme.rope_theta ** (-2 * i / dim) for i in range(dim // 2)]
    if scheme.factor is None:
        return plain

    def turns_at(rotations):
        return dim * math.log(scheme.original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(scheme.rope_theta))

    low = max(math.floor(turns_at(scheme.beta_fast)), 0)
    high = min(math.ceil(turns_at(scheme.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / scheme.factor * ramp + f * (1.0 - ramp))
    return out


def rope_partial(x, positions, inv_freq, scaling: float = 1.0):
    """Rotary embedding on the FIRST ``2 * len(inv_freq)`` depths of
    ``x [..., T, H, D]`` (half-split pairs ``(i, i + len(inv_freq))`` as
    ``models/llama.py rope``), cos and sin times ``scaling``; the other
    depths pass as they are."""
    half = len(inv_freq)
    freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = (jnp.cos(angles) * scaling)[..., :, None, :]
    sin = (jnp.sin(angles) * scaling)[..., :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), rest], axis=-1)


def head_gate(logits):
    """A gate a head and position from its logits ``[..., T, H]``: the
    sigmoid, in float32."""
    return jax.nn.sigmoid(logits.astype(jnp.float32))


def _dense(c, features, names, name):
    return nn.Dense(features, use_bias=False, dtype=c.dtype, name=name,
                    kernel_init=_part(nn.initializers.lecun_normal(), names))


class GatedAttention(nn.Module):
    """One layer's attention over the heads held here, full or windowed."""

    cfg: WindowMoEConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions):
        c, kind = self.cfg, self.cfg.layer_types[self.layer]
        if kind not in (FULL, SLIDING):
            raise ValueError(f"layer_types[{self.layer}] = {kind!r}")
        B, T, _ = x.shape
        H, KV, d = c.heads(self.layer), c.kv_heads, c.head_dim
        window = c.sliding_window if kind == SLIDING else None
        _telemetry.inc("hvd_attn_layers_total", kind=kind, heads=H,
                       kv_heads=KV)
        with jax.named_scope("hvd_attn_window" if window else
                             "hvd_attn_full"):
            q = _dense(c, H * d, ("embed", "heads"), "wq")(x)
            k = _dense(c, KV * d, ("embed", "kv_heads"), "wk")(x)
            v = _dense(c, KV * d, ("embed", "kv_heads"), "wv")(x)
            q = q.reshape(B, T, H, d)
            k = k.reshape(B, T, KV, d)
            v = v.reshape(B, T, KV, d)
            scheme = c.rope_sliding if kind == SLIDING else c.rope_full
            inv_freq = yarn_inv_freq(scheme, d)
            q = rope_partial(q, positions, inv_freq, scheme.attention_factor)
            k = rope_partial(k, positions, inv_freq, scheme.attention_factor)
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
            scale = 1.0 / d ** 0.5
            if _resolve_flash(c.use_flash, T):
                from ..ops.flash_attention import flash_attention
                o = flash_attention(q, k, v, causal=True, scale=scale,
                                    window=window)
            else:
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                    jnp.float32) * scale
                i = jnp.arange(T)
                seen = i[:, None] >= i[None, :]
                if window:
                    seen &= i[:, None] - i[None, :] < window
                s = jnp.where(seen[None, None], s, -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                from jax.ad_checkpoint import checkpoint_name
                o = checkpoint_name(
                    jnp.einsum("bhqk,bkhd->bqhd", p, v), "attn_out")
            with jax.named_scope("hvd_head_gate"):
                g = head_gate(_dense(c, H, ("embed", "heads"), "wg")(x))
                o = o * g[..., None].astype(o.dtype)
            return _dense(c, c.hidden_size, ("heads", "embed"), "wo")(
                o.reshape(B, T, H * d))


class PatternBlock(nn.Module):
    """``y = x + Attn(norm(x)); z = y + FFN(norm(y))``, both parts by the
    layer's entries in the pattern's lists."""

    cfg: WindowMoEConfig
    layer: int = 0
    train: bool = False     # an attribute: static under ``nn.remat``

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, c.dtype, name=name)
        x = x + GatedAttention(c, self.layer, name="attn")(
            norm("attn_norm")(x), positions)
        h = norm("mlp_norm")(x)
        kind = c.mlp_layer_types[self.layer]
        if kind == "dense":
            return x + MLP(c.dense_mlp, name="mlp")(h)
        if kind != "sparse":
            raise ValueError(f"mlp_layer_types[{self.layer}] = {kind!r}")
        return x + ExpertLayer(c.expert_layer, self.train, name="moe")(h)


class WindowMoE(nn.Module):
    cfg: WindowMoEConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        L = c.num_hidden_layers
        if not (len(c.mlp_layer_types) == L
                == len(c.num_attention_heads_per_layer)):
            raise ValueError("the three per-layer lists differ in length")
        if any(n % c.num_key_value_heads
               for n in c.num_attention_heads_per_layer):
            raise ValueError("a layer's heads do not divide over the KV "
                             "heads")
        emb = self.param("embedding", _part(nn.initializers.normal(0.02),
                                            ("vocab", "embed_table")),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        head = self.param("lm_head", _part(nn.initializers.lecun_normal(),
                                           ("embed", "vocab")),
                          (c.hidden_size, c.vocab_size), jnp.float32)
        # unrolled layers: the recomputation needs its CSE barrier
        block = _remat(PatternBlock, c.remat_policy, prevent_cse=True) \
            if c.remat else PatternBlock
        positions = jnp.arange(tokens.shape[1])[None, :]
        x = jnp.take(emb, tokens, axis=0).astype(c.dtype)
        for i in range(L):
            x = block(c, layer=i, train=train, name=f"layer_{i}")(
                x, positions)
        x = RMSNorm(c.rms_norm_eps, c.dtype, name="final_norm")(x)
        # operands in the compute type, float32 accumulation and logits
        # (as ``models/llama.py``'s head)
        return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype),
                          preferred_element_type=jnp.float32)
