"""Plain reference for the looped-decoder family: forward pass, the four
heads, the exit gate's distribution, the weighed loss with its entropy term,
gradients and AdamW in straightforward ``jax.numpy`` and float32, matrix
multiplications at ``highest`` precision. No flax module, no kernel, nothing
of the library under test; the flax parameter tree is read by name.

The equations (arXiv:2510.25741 and the published modeling file, as
configs/ouro-2.6b.json "assumed" says): positions ``i``, passes ``t = 1..T``,
layers ``l = 1..L``, every norm an RMSNorm with its own scale.

- block: ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``; causal
  attention, rotate-half rotary over the whole head depth, scale
  ``1/sqrt(head depth)``; ``MLP(z) = W2 (silu(W1 z) * W3 z)``;
- loop: ``h0 = Embed(tokens)``, ``ht = Nf(Stack(h(t-1)))``, the same
  ``Stack`` and ``Nf`` every pass;
- after each pass: ``zt = ht W_head``, ``lt = sigmoid(w_g . ht + b_g)``;
- ``pt = lt prod_{j<t} (1 - lj)`` for ``t < T``, ``pT = prod_{j<T} (1 - lj)``;
- loss: ``mean_i [ sum_t pt_i CE(zt_i, y_i) - beta H(p_i) ]``.

A Python loop over the layers inside a ``lax.scan`` over the passes (the same
pass traced once where a Python loop traces it T times: at the published
widths 72 s of compile for 169 and 0.9 GB less, ahead of time for the chip),
the gradient of the shared weights by ``jax.grad`` (which sums over the
passes by itself), plain ``optax.adamw``.

Departures, memory only and the same arithmetic, so that float32 at 4,096
tokens fits beside 16 bytes a parameter of its own state: every layer
application maps over the sequences one at a time with a ``jax.checkpoint``
a sequence, attention materialises the scores of a block of heads at a time
(checkpointed), and a pass's logits exist 1,024 positions at a time inside a
checkpointed cross entropy. Everything runs on the first device.
"""

import math
import types


def build(config):
    """The family's mathematics for ``config``: ``hidden`` (the passes'
    normed states), ``exit_distribution``, ``losses`` (every pass's
    per-token cross entropy), ``loss`` and the un-jitted ``init``, ``step``."""
    import jax
    import jax.numpy as jnp
    import optax

    c = config
    L, T = c["num_hidden_layers"], c["total_ut_steps"]
    H, d = c["num_attention_heads"], c["hidden_size"]
    dh = d // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    beta = config["assumed"]["exit_entropy_weight"]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)
    heads_a_block = 2 if H % 2 == 0 else 1

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    def rope(x, positions):
        """[S, heads, dh]: rotate the halves (i, i + dh/2)."""
        half = dh // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions[:, None, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(x, p, pre):
        S = x.shape[0]
        pos = jnp.arange(S)
        q = rope((x @ p[pre + "wq/kernel"]).reshape(S, H, dh), pos)
        k = rope((x @ p[pre + "wk/kernel"]).reshape(S, H, dh), pos)
        v = (x @ p[pre + "wv/kernel"]).reshape(S, H, dh)

        @jax.checkpoint
        def block_of_heads(qkv):
            qb, kb, vb = qkv                                  # [hb, S, dh]
            s = jnp.einsum("hqd,hkd->hqk", qb, kb) / math.sqrt(dh)
            s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), vb)

        blocks = lambda t: t.transpose(1, 0, 2).reshape(
            H // heads_a_block, heads_a_block, S, dh)
        o = jax.lax.map(block_of_heads, (blocks(q), blocks(k), blocks(v)))
        return o.reshape(H, S, dh).transpose(1, 0, 2).reshape(S, d) \
            @ p[pre + "wo/kernel"]

    def mlp(x, p, pre):
        return (jax.nn.silu(x @ p[pre + "w1/kernel"])
                * (x @ p[pre + "w3/kernel"])) @ p[pre + "w2/kernel"]

    def layer(x, p, name):
        """One block over every sequence, one sequence at a time."""
        pre = name + "/"

        def one(x):
            a = x + rms(attention(rms(x, p[pre + "attn_norm/scale"]), p,
                                  pre + "attn/"),
                        p[pre + "attn_post_norm/scale"])
            return a + rms(mlp(rms(a, p[pre + "mlp_norm/scale"]), p,
                               pre + "mlp/"),
                           p[pre + "mlp_post_norm/scale"])

        return jax.lax.map(jax.checkpoint(one), x)

    def hidden(p, tokens):
        """The T passes' normed states, each ``[B, S, d]``."""
        def one_pass(h, _):
            for i in range(L):
                h = layer(h, p, f"layer_{i}")
            h = rms(h, p["final_norm/scale"])
            return h, h

        _, states = jax.lax.scan(one_pass, p["embedding"][tokens], None,
                                 length=T)
        return list(states)

    def exit_distribution(p, states):
        """``[p1, ..., pT]``, each ``[B, S]``."""
        left, out = 1.0, []
        for h in states[:-1]:
            lam = jax.nn.sigmoid(
                (h @ p["exit_gate/kernel"])[..., 0] + p["exit_gate/bias"][0])
            out.append(lam * left)
            left = left * (1.0 - lam)
        return out + [left * jnp.ones(states[-1].shape[:-1], jnp.float32)]

    def losses(p, states, labels):
        """Every pass's per-token cross entropy, each ``[B, S]``."""
        @jax.checkpoint
        def ce(block):
            h, targets = block                      # [n, d], [n]
            z = h @ p["lm_head"]
            return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
                z, targets[:, None], -1)[:, 0]

        n = math.gcd(labels.size, 1024)             # positions a block
        return [jax.lax.map(ce, (h.reshape(-1, n, d), labels.reshape(-1, n))
                            ).reshape(labels.shape) for h in states]

    def loss(p, tokens, labels):
        states = hidden(p, tokens)
        shares = exit_distribution(p, states)
        expected = sum(s * ce for s, ce in zip(shares,
                                               losses(p, states, labels)))
        entropy = sum(jax.scipy.special.entr(s) for s in shares)
        return (expected - beta * entropy).mean()

    def step(p, opt_state, batch):
        value, grads = jax.value_and_grad(loss)(p, *batch)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, value

    return types.SimpleNamespace(
        hidden=hidden, exit_distribution=exit_distribution, losses=losses,
        loss=loss, init=optimizer.init, step=step)


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    ref = build(config)
    return jax.jit(ref.init), jax.jit(ref.step, donate_argnums=(0, 1))
