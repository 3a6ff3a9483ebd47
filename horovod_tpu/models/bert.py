"""BERT-style bidirectional encoder (GSPMD-sharded) + MLM pretraining head.

Role: BASELINE.md config 2 (BERT-Large pretraining — fp16 compression +
tensor-fusion allreduce in the reference; here the grad sync is the in-graph
psum and fusion is the XLA combiner, with bf16 compute standing in for the
fp16 wire). Sharding uses the same logical rule table as llama.py
(LOGICAL_RULES): tp shards heads/mlp, dp/fsdp shard the batch.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from .llama import _part, _remat
from ._flash import resolve_flash as _resolve_flash


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    hidden_dim: int = 4096
    max_seq_len: int = 512
    type_vocab: int = 2
    norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"  # see models/llama.py LlamaConfig
    # None = auto: Pallas flash attention on TPU, materialised softmax
    # elsewhere (interpret-mode Pallas is too slow for CPU test meshes).
    use_flash: "bool | None" = None


def bert_large() -> BertConfig:
    return BertConfig()


def bert_base() -> BertConfig:
    return BertConfig(dim=768, n_layers=12, n_heads=12, hidden_dim=3072)


def bert_tiny(vocab: int = 256) -> BertConfig:
    return BertConfig(vocab_size=vocab, dim=64, n_layers=2, n_heads=4,
                      hidden_dim=128, max_seq_len=128, dtype=jnp.float32,
                      remat=False)


class EncoderBlock(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attn_mask):
        c = self.cfg
        head_dim = c.dim // c.n_heads
        B, T, _ = x.shape
        dense = lambda feats, names, name: nn.Dense(
            feats, use_bias=True, dtype=c.dtype, name=name,
            kernel_init=_part(nn.initializers.lecun_normal(), names))
        h = x
        q = dense(c.dim, ("embed", "heads"), "wq")(h)
        k = dense(c.dim, ("embed", "heads"), "wk")(h)
        v = dense(c.dim, ("embed", "heads"), "wv")(h)
        q = q.reshape(B, T, c.n_heads, head_dim)
        k = k.reshape(B, T, c.n_heads, head_dim)
        v = v.reshape(B, T, c.n_heads, head_dim)
        if _resolve_flash(c.use_flash, T):
            from ..ops.flash_attention import flash_attention
            o = flash_attention(q, k, v, causal=False,
                                kv_mask=attn_mask,
                                scale=float(1.0 / head_dim ** 0.5))
        else:
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            s = s / jnp.sqrt(head_dim)
            s = jnp.where(attn_mask[:, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        o = o.reshape(B, T, c.dim)
        o = dense(c.dim, ("heads", "embed"), "wo")(o)
        x = nn.LayerNorm(epsilon=c.norm_eps, dtype=c.dtype,
                         name="attn_norm")(x + o)
        f = dense(c.hidden_dim, ("embed", "mlp"), "ffn_in")(x)
        f = nn.gelu(f)
        f = nn_partitioning.with_sharding_constraint(
            f, ("batch", "seq", "mlp"))
        f = dense(c.dim, ("mlp", "embed"), "ffn_out")(f)
        x = nn.LayerNorm(epsilon=c.norm_eps, dtype=c.dtype,
                         name="ffn_norm")(x + f)
        return x


class Bert(nn.Module):
    """Returns MLM logits [B, T, vocab]. ``attn_mask`` marks real tokens."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, tokens, attn_mask=None, train: bool = True):
        c = self.cfg
        if attn_mask is None:
            attn_mask = jnp.ones_like(tokens, bool)
        # "embed_table" (→ no fsdp): gather/slice operands sharded over
        # fsdp trigger SPMD involuntary rematerialization — see llama.py.
        emb = self.param("tok_embedding",
                         _part(nn.initializers.normal(0.02),
                               ("vocab", "embed_table")),
                         (c.vocab_size, c.dim), jnp.float32)
        pos = self.param("pos_embedding",
                         _part(nn.initializers.normal(0.02),
                               ("seq", "embed_table")),
                         (c.max_seq_len, c.dim), jnp.float32)
        T = tokens.shape[1]
        x = jnp.take(emb, tokens, axis=0) + pos[None, :T]
        x = nn.LayerNorm(epsilon=c.norm_eps, dtype=c.dtype,
                         name="embed_norm")(x.astype(c.dtype))
        x = nn_partitioning.with_sharding_constraint(
            x, ("batch", "seq", "embed"))
        block = _remat(EncoderBlock, c.remat_policy) if c.remat \
            else EncoderBlock
        for i in range(c.n_layers):
            x = block(c, name=f"layer_{i}")(x, attn_mask)
        # MLM head: transform + tied output embedding (standard BERT).
        x = nn.Dense(c.dim, dtype=c.dtype, name="mlm_transform",
                     kernel_init=_part(nn.initializers.lecun_normal(),
                                       ("embed", "embed_fsdp")))(x)
        x = nn.gelu(x)
        x = nn.LayerNorm(epsilon=c.norm_eps, dtype=c.dtype,
                         name="mlm_norm")(x)
        # Deliberately f32xf32 (NOT the llama.py bf16-operand head): the
        # bf16+f32-accum variant measured 0.5% SLOWER interleaved at the
        # BERT-Large shape — XLA already decomposes this f32 matmul
        # efficiently at [4096, 1024] x [1024, 30522] (a builder's chip run
        # before PR 1).
        logits = jnp.einsum("btd,vd->btv", x.astype(jnp.float32), emb)
        return logits


def mlm_loss(logits, labels, mask):
    """Masked-LM cross entropy over positions where ``mask`` is set.

    ``logsumexp - target_logit`` rather than a materialized
    ``log_softmax``: the [B,T,V] f32 log-probs cost an extra HBM
    write+read per step for values immediately reduced away (the
    next_token_loss rationale, train.py)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    m = mask.astype(nll.dtype)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
