"""Looped decoder: ONE stack of layers applied ``total_ut_steps`` times over
the same weights, a head and a loss after every pass, and a learned exit gate
that weighs them (Zhu et al., "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741; the benchmark runs Ouro-2.6B's published widths
through it).

Named for its mechanism, configured by the keys of the published
``config.json``. With positions ``i``, passes ``t = 1..T`` and layers
``l = 1..L`` (every norm an RMSNorm with a scale of its own, no bias but the
gate's):

- **Block** (norms before AND after each sub-layer): ``a = x + N2(Attn(N1(x)))``,
  ``y = a + N4(MLP(N3(a)))``; ``Attn`` and ``MLP`` are ``models/llama.py``'s
  (causal, rotate-half rotary over the whole head depth, SwiGLU).
- **Loop**: ``h0 = Embed(tokens)``, ``ht = Nf(Stack(h(t-1)))`` with the same
  ``Stack`` and the same final norm ``Nf`` every pass; the NORMED state is
  what the next pass reads. Every layer's weight gradient is a sum over its
  ``T`` uses, and the backward pass keeps ``T x L`` layer applications'
  residuals on ``L`` layers' weights.
- **After each pass**: logits ``zt = ht W_head`` and a gate
  ``lt = sigmoid(w_g . ht + b_g)`` a position (float32).
- **Exit distribution** a position: ``pt = lt prod_{j<t} (1 - lj)`` for
  ``t < T`` and ``pT = prod_{j<T} (1 - lj)``; it sums to one.
- **Loss** (the paper's stage-I objective, :func:`looped_loss`):
  ``mean_i [ sum_t pt_i CE(zt_i, y_i) - beta H(p_i) ]``.

``__call__(tokens [B, S], train)`` returns a :class:`LoopedOutput`: the
per-pass normed states ``[T, B, S, d]``, the gate's logits ``[T, B, S]`` and
the head ``[d, V]``, NOT four logit arrays: at the published vocabulary one
pass's float32 logits are 0.8 GB at 4,096 tokens, so the loss makes each
pass's logits and cross entropy under ``jax.checkpoint`` inside a loop over
the passes, and one pass's logits and their cotangent are the most that
lives. ``LoopedOutput.logits(t)`` gives a pass's logits to who wants them.
The mean exit distribution of the batch rides ``TrainState.batch_stats``
(leaf ``exit_share`` ``[T]``) as a router's counts do; :func:`exit_gauges`
turns it into gauges on the host.

Not here: the gate's stage-II training (gate alone, model frozen) and
adaptive early exit at inference (ROADMAP "Reach").
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry as _telemetry
from .llama import MLP, Attention, LlamaConfig, RMSNorm, _part, _remat


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    # keys of the published config.json, under their names
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    # not in config.json
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # What the backward pass keeps of a layer application besides its input
    # (``models/llama.py _REMAT_POLICIES``). The applications are T times as
    # many as the layers whose state shares the memory with them.
    remat_policy: str = "attn"
    use_flash: "bool | None" = None

    @property
    def layer(self) -> LlamaConfig:
        """The sub-layers' configuration, in ``models/llama.py``'s names."""
        return LlamaConfig(
            vocab_size=self.vocab_size, dim=self.hidden_size,
            n_layers=self.num_hidden_layers, n_heads=self.num_attention_heads,
            n_kv_heads=self.num_key_value_heads,
            hidden_dim=self.intermediate_size, rope_theta=self.rope_theta,
            norm_eps=self.rms_norm_eps, dtype=self.dtype,
            use_flash=self.use_flash)


def looped_tiny(vocab: int = 128, **over) -> LoopedConfig:
    """CPU-mesh test configuration: 2 layers, 3 passes, 64 wide."""
    return LoopedConfig(**{**dict(
        vocab_size=vocab, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        rope_theta=10000.0, total_ut_steps=3, dtype=jnp.float32,
        remat=False), **over})


class SandwichBlock(nn.Module):
    """``a = x + N2(Attn(N1(x))); y = a + N4(MLP(N3(a)))``."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, positions):
        c = self.cfg
        norm = lambda name: RMSNorm(c.norm_eps, c.dtype, name=name)
        x = x + norm("attn_post_norm")(
            Attention(c, name="attn")(norm("attn_norm")(x), positions))
        return x + norm("mlp_post_norm")(
            MLP(c, name="mlp")(norm("mlp_norm")(x)))


class LoopedOutput(NamedTuple):
    hidden: Any     # [T, B, S, d] each pass's normed state, compute type
    gate: Any       # [T, B, S] float32 gate logits (the last is not used)
    head: Any       # [d, V] float32

    def logits(self, t: int = -1):
        """Pass ``t``'s float32 logits ``[B, S, V]`` (default: the last)."""
        return _head_logits(self.hidden[t], self.head)


def _head_logits(h, head):
    """Operands in the compute type, float32 accumulation and logits (as
    ``models/llama.py``'s head)."""
    return jnp.einsum("bsd,dv->bsv", h, head.astype(h.dtype),
                      preferred_element_type=jnp.float32)


def exit_log_distribution(gate):
    """``log p`` ``[T, ...]`` from the gate's logits ``[T, ...]``:
    ``p_t = l_t prod_{j<t} (1 - l_j)`` with ``l = sigmoid(gate)``, the last
    pass taking what is left. In logarithms, so that a saturated gate gives
    a zero share and not a NaN entropy."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate[:-1]), axis=0)
    first = jnp.zeros_like(gate[:1])
    # log prod_{j<t} (1 - l_j), and log l_t but for the last pass
    return jnp.concatenate([first, stay], axis=0) + jnp.concatenate(
        [jax.nn.log_sigmoid(gate[:-1]), first], axis=0)


class Looped(nn.Module):
    cfg: LoopedConfig

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        T, L = c.total_ut_steps, c.num_hidden_layers
        if c.hidden_size % c.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")
        _telemetry.inc("hvd_loop_models_total", layers=L, passes=T)
        emb = self.param("embedding", _part(nn.initializers.normal(0.02),
                                            ("vocab", "embed_table")),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        head = self.param("lm_head", _part(nn.initializers.lecun_normal(),
                                           ("embed", "vocab")),
                          (c.hidden_size, c.vocab_size), jnp.float32)
        # unrolled layers: the recomputation needs its CSE barrier
        block = _remat(SandwichBlock, c.remat_policy, prevent_cse=True) \
            if c.remat else SandwichBlock
        # ONE set of modules, called once a pass: the parameters are shared
        layers = [block(c.layer, name=f"layer_{i}") for i in range(L)]
        final_norm = RMSNorm(c.rms_norm_eps, c.dtype, name="final_norm")
        gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate",
                        kernel_init=_part(nn.initializers.lecun_normal(),
                                          ("embed", None)))
        positions = jnp.arange(tokens.shape[1])[None, :]
        x = jnp.take(emb, tokens, axis=0).astype(c.dtype)
        hidden, gates = [], []
        for _ in range(T):
            with jax.named_scope("hvd_loop_pass"):
                for layer in layers:
                    x = layer(x, positions)
                x = final_norm(x)
            hidden.append(x)
            with jax.named_scope("hvd_exit_gate"):
                gates.append(gate(x.astype(jnp.float32))[..., 0])
        out = LoopedOutput(jnp.stack(hidden), jnp.stack(gates), head)
        share = self.variable("batch_stats", "exit_share", jnp.zeros, (T,),
                              jnp.float32)
        if train and self.is_mutable_collection("batch_stats"):
            with jax.named_scope("hvd_exit_gate"):
                share.value = jax.lax.stop_gradient(jnp.exp(
                    exit_log_distribution(out.gate)).mean(axis=(1, 2)))
        return out


def pass_cross_entropies(hidden, head, labels):
    """Per-token cross entropy of every pass, ``[T, B, S]`` float32. A loop
    over the passes whose body is rematerialised: forward keeps no logits,
    backward makes one pass's again and holds them with their cotangent."""
    @jax.checkpoint
    def one_pass(h):
        with jax.named_scope("hvd_loop_head"):
            z = _head_logits(h, head)
            return jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
                z, labels[..., None], axis=-1)[..., 0]
    return jax.lax.map(one_pass, hidden)


def looped_loss(beta: float = 0.1):
    """``loss_fn(out, labels)`` for ``make_train_step``: ``out`` a
    :class:`LoopedOutput`, labels ``[B, S]`` the ids one to the right of the
    model's input. The passes' cross entropies weighed by the exit
    distribution, less ``beta`` times its entropy, a mean over positions."""
    def loss_fn(out, labels):
        ce = pass_cross_entropies(out.hidden, out.head, labels)
        with jax.named_scope("hvd_exit_gate"):
            logp = exit_log_distribution(out.gate)
            p = jnp.exp(logp)
            return ((p * ce).sum(0) + beta * (p * logp).sum(0)).mean()
    return loss_fn


def exit_gauges(exit_share):
    """Host side, when the user calls it (it fetches ``exit_share``, the
    ``[T]`` leaf of ``TrainState.batch_stats``, so not inside a run-ahead
    loop's dispatch): gauges ``hvd_loop_exit_share{pass=}`` (1-based) and
    ``hvd_loop_exit_mean_pass``, and the same as a dict."""
    share = np.asarray(exit_share, np.float64)
    mean_pass = float((share * np.arange(1, len(share) + 1)).sum())
    for t, value in enumerate(share, start=1):
        _telemetry.set_gauge("hvd_loop_exit_share", float(value),
                             **{"pass": t})
    _telemetry.set_gauge("hvd_loop_exit_mean_pass", mean_pass)
    return {"exit_share": share.tolist(), "exit_mean_pass": mean_pass}
