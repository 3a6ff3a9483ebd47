"""Mixtral-style sparse-MoE decoder transformer (GSPMD, expert-parallel).

Role: BASELINE.md config 4 (Mixtral-8x7B — alltoall expert dispatch over
ICI). The reference only exposes the alltoall *primitive* (SURVEY.md §2.6
"EP: primitive only"); this is the full layer: Llama blocks whose FFN is a
top-2 routed bank of SwiGLU experts. Experts are sharded over the ``ep``
mesh axis ("experts" logical axis); the sort-based gather-only
dispatch/combine (parallel/moe.py, r4 — the one-hot [T,E,C] einsums it
replaced profiled costlier than the expert matmuls) feeds expert buffers
whose sharding constraints make XLA lower the exchange to all_to_all over
ICI — the GSPMD twin of ``parallel.moe.routed_experts`` (the explicit
shard_map version, tested equivalent).

Aux load-balancing losses are sown into the ``losses`` collection; the train
harness (make_gspmd_train_step(aux_weight=...)) folds them into the loss.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import partitioning as nn_partitioning

from ..parallel.moe import sorted_combine, sorted_dispatch, topk_router_sorted
from .llama import Attention, LlamaConfig, RMSNorm, _part


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.02


def mixtral_8x7b() -> MixtralConfig:
    return MixtralConfig(vocab_size=32000, dim=4096, n_layers=32,
                         n_heads=32, n_kv_heads=8, hidden_dim=14336,
                         rope_theta=1e6, n_experts=8, top_k=2)


def mixtral_tiny(vocab: int = 256) -> MixtralConfig:
    return MixtralConfig(vocab_size=vocab, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                         dtype=jnp.float32, remat=False, scan_layers=False,
                         n_experts=8, top_k=2, capacity_factor=2.0)


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU expert bank, experts sharded over ``ep``."""

    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        B, T, D = x.shape
        E, M = c.n_experts, c.hidden_dim
        router = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router",
                          kernel_init=_part(nn.initializers.lecun_normal(),
                                            ("embed", None)))
        w1 = self.param("w1", _part(nn.initializers.lecun_normal(),
                                    ("experts", "embed", "mlp")), (E, D, M))
        w3 = self.param("w3", _part(nn.initializers.lecun_normal(),
                                    ("experts", "embed", "mlp")), (E, D, M))
        w2 = self.param("w2", _part(nn.initializers.lecun_normal(),
                                    ("experts", "mlp", "embed")), (E, M, D))

        tokens = x.reshape(B * T, D)
        logits = router(tokens)
        capacity = max(1, int(c.capacity_factor * c.top_k * B * T / E))
        # Sort-based dispatch plan (r4): the one-hot [T,E,C] einsum
        # dispatch cost more device time than the expert matmuls at the
        # toy-width config a builder traced before PR 1 — row
        # gather/scatter moves O(k·T·D) bytes instead.
        r = topk_router_sorted(logits, E, capacity, c.top_k)
        self.sow("losses", "router_aux", r.aux_loss)

        dispatched = sorted_dispatch(tokens, r, E, capacity)  # [E,C,D]
        dispatched = nn_partitioning.with_sharding_constraint(
            dispatched, ("experts", None, "embed"))
        h = jax.nn.silu(jnp.einsum("ecd,edm->ecm", dispatched,
                                   w1.astype(c.dtype)))
        h = h * jnp.einsum("ecd,edm->ecm", dispatched, w3.astype(c.dtype))
        h = nn_partitioning.with_sharding_constraint(
            h, ("experts", None, "mlp"))
        out = jnp.einsum("ecm,emd->ecd", h, w2.astype(c.dtype))
        y = sorted_combine(out, r, B * T).astype(c.dtype)
        return y.reshape(B, T, D)


class MixtralBlock(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        x = x + Attention(c, name="attn")(
            RMSNorm(c.norm_eps, c.dtype, name="attn_norm")(x), positions)
        x = x + MoEMLP(c, name="moe")(
            RMSNorm(c.norm_eps, c.dtype, name="mlp_norm")(x))
        return x


class ScannedMixtralBlock(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, positions):
        return MixtralBlock(self.cfg, name="block")(x, positions), None


class Mixtral(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        from .llama import decoder_trunk
        return decoder_trunk(self, self.cfg, tokens, MixtralBlock,
                             ScannedMixtralBlock,
                             extra_scan_collections=("losses",))
