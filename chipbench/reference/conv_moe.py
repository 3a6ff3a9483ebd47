"""Plain reference for the short-convolution / attention, routed-experts
family: forward pass, next-token cross entropy, gradients, AdamW and the
router's bias update in straightforward ``jax.numpy`` and float32, matrix
multiplications at ``highest`` precision. No flax module, no kernel, no
sort, no grouped product, nothing of the library under test; the flax
parameter tree is read by name.

The equations (configs/lfm2-8b-a1b.json "assumed"; ``transformers``'
``Lfm2ShortConv`` and ``Lfm2MoeSparseMoeBlock``), for x ``[T, D]`` and
``h = RMSNorm(x)``:

- ``conv`` layer: ``[B | C | u] = h W_in`` split in that order, ``v = B *
  u``, ``z_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t`` a channel (zero before
  position 0; ``L = conv_L_cache`` taps in general), ``y = x + (C * z)
  W_out``;
- ``full_attention`` layer: ``q = RMSNorm_q(h Wq)``, ``k = RMSNorm_k(h
  Wk)`` over each head's depths, ``v = h Wv``; rotary on half-split pairs,
  ``theta ** (-2i / d)``; query head ``i`` reads KV head ``i // (H / KV)``,
  scores ``q . k / sqrt(d)`` over keys ``j <= i``; ``y = x + o Wo``;
- ``b = RMSNorm(y)``; below ``num_dense_layers`` ``z = y + W2 (silu(W1 b)
  * W3 b)``; above it ``s = sigmoid(b Wr)`` over ALL experts, the
  ``num_experts_per_tok`` largest of ``s + bias`` chosen, ``gamma_i =
  scaling * s_i / sum_chosen s``, ``z = y + sum over chosen HELD experts of
  gamma_i E_i(b)``: every token through every held expert under a gate that
  is 0 unless chosen;
- ``logits = RMSNorm(z_last) E^T`` with the embedding table ``E``; the mean
  next-token cross entropy; after the update each bias moves by ``rate *
  sign(mean count - count)`` over the global batch.

Departures, memory only and the same arithmetic, so that float32 at 8,192
tokens fits beside 16 bytes a parameter of its own state: every layer maps
over the sequences one at a time with a ``jax.checkpoint`` a sequence,
attention materialises the scores of one KV head's query heads and one
block of 512 queries at a time (checkpointed), the held experts are scanned
(checkpointed), and logits exist 1,024 positions at a time inside a
checkpointed cross entropy. Everything runs on the first device.
"""

import math
import types


def build(config):
    """The family's mathematics for ``config``: ``conv`` (one conv layer's
    mixer, before the residual), ``attention``, ``experts`` (one sparse
    layer's feed-forward part and its counts), ``logits``, ``loss`` and the
    un-jitted ``init`` and ``step``."""
    import jax
    import jax.numpy as jnp
    import optax

    c, a = config, config["assumed"]
    D, H, KV = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    d, G, eps = D // H, H // KV, c["norm_eps"]
    kinds, n_dense = c["layer_types"], c["num_dense_layers"]
    held = c["num_experts"]
    n_experts = config["published"]["num_experts"]
    first = config["deployment"]["first_expert"]
    top_k, scaling = c["num_experts_per_tok"], c["routed_scaling_factor"]
    rate = a["bias_update_rate"] if c["use_expert_bias"] else 0.0
    expert_layers = [f"layer_{i}" for i in range(n_dense, len(kinds))]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    def conv(x, p, pre):
        """``(C * z) W_out`` of one sequence ``x [T, D]`` (normed)."""
        bcu = x @ p[pre + "in_proj/kernel"]
        b, gate, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
        v = b * u
        taps = p[pre + "taps"]                          # [L, D]
        L = taps.shape[0]
        z = jnp.zeros_like(v)
        for j in range(L):      # tap j reads v_{t - (L - 1 - j)}
            shift = L - 1 - j
            z = z + taps[j] * jnp.concatenate(
                [jnp.zeros((shift, D), v.dtype), v[:v.shape[0] - shift]])
        return (gate * z) @ p[pre + "out_proj/kernel"]

    def rotary(x, positions):
        """[T, heads, d]: rotate the pairs (i, i + d/2)."""
        half = d // 2
        freqs = c["rope_theta"] ** (-2 * jnp.arange(half, dtype=jnp.float32)
                                    / d)
        ang = positions[:, None, None].astype(jnp.float32) * freqs
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    def attention(x, p, pre):
        """``o Wo`` of one sequence ``x [T, D]`` (normed)."""
        T = x.shape[0]
        pos = jnp.arange(T)
        q = rms((x @ p[pre + "wq/kernel"]).reshape(T, H, d),
                p[pre + "q_norm/scale"])
        k = rms((x @ p[pre + "wk/kernel"]).reshape(T, KV, d),
                p[pre + "k_norm/scale"])
        v = (x @ p[pre + "wv/kernel"]).reshape(T, KV, d)
        q, k = rotary(q, pos), rotary(k, pos)
        bq = math.gcd(T, 512)

        @jax.checkpoint
        def one_kv_head(args):
            qh, kh, vh = args               # [T, G, d], [T, d], [T, d]

            @jax.checkpoint
            def block(args):
                qb, pb = args               # [bq, G, d], [bq]
                s = jnp.einsum("qgd,kd->gqk", qb, kh) / math.sqrt(d)
                s = jnp.where(pb[None, :, None] >= pos[None, None, :], s,
                              -jnp.inf)
                return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, -1), vh)

            return jax.lax.map(block, (qh.reshape(T // bq, bq, G, d),
                                       pos.reshape(T // bq, bq))
                               ).reshape(T, G, d)

        o = jax.lax.map(one_kv_head, (
            q.reshape(T, KV, G, d).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [KV, T, G, d]
        return o.transpose(1, 0, 2, 3).reshape(T, H * d) \
            @ p[pre + "wo/kernel"]

    def swiglu(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def experts(x, p, pre, bias):
        """``(the held experts' gated outputs, tokens that chose each
        expert)``."""
        s = jax.nn.sigmoid(x @ p[pre + "router/kernel"])
        _, choice = jax.lax.top_k(s + bias, top_k)
        chosen = jax.nn.one_hot(choice, n_experts).sum(1)        # [T, E] 0/1
        gates = scaling * s * chosen / (s * chosen).sum(-1, keepdims=True)

        def add_expert(y, expert):      # every token through every expert,
            gate, w1, w3, w2 = expert   # weighed by a gate that is 0 unless
            return y + gate[:, None] * swiglu(x, w1, w3, w2), None   # chosen

        y, _ = jax.lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(x), (
            gates[:, first:first + held].T, p[pre + "w1"], p[pre + "w3"],
            p[pre + "w2"]))
        return y, chosen.sum(0)

    def layer(x, p, i, bias):
        """One block over every sequence, one sequence at a time, and the
        tokens that chose each expert (0 in a dense layer)."""
        pre = f"layer_{i}/"

        def one(x):
            h = rms(x, p[pre + "operator_norm/scale"])
            if kinds[i] == "conv":
                x = x + conv(h, p, pre + "conv/")
            else:
                x = x + attention(h, p, pre + "self_attn/")
            h = rms(x, p[pre + "ffn_norm/scale"])
            if bias is None:
                return x + swiglu(h, *(p[f"{pre}feed_forward/{w}/kernel"]
                                       for w in ("w1", "w3", "w2"))), 0.0
            y, counts = experts(h, p, pre + "feed_forward/", bias)
            return x + y, counts

        x, counts = jax.lax.map(jax.checkpoint(one), x)
        return x, jnp.sum(counts, 0)

    def hidden(p, biases, tokens):
        x, counts = p["embedding"][tokens], {}
        for i in range(len(kinds)):
            name = f"layer_{i}"
            x, counts[name] = layer(x, p, i, biases.get(name))
        return rms(x, p["embedding_norm/scale"]), counts

    def logits(p, biases, tokens):
        return hidden(p, biases, tokens)[0] @ p["embedding"].T

    def loss(p, biases, tokens, labels):
        h, counts = hidden(p, biases, tokens)

        @jax.checkpoint
        def ce(block):
            hb, targets = block                     # [n, D], [n]
            z = hb @ p["embedding"].T
            return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
                z, targets[:, None], -1)[:, 0]

        n = math.gcd(labels.size, 1024)             # positions a block
        return jax.lax.map(ce, (h.reshape(-1, n, D),
                                labels.reshape(-1, n))).mean(), counts

    def init(p):
        return optimizer.init(p), {name: jnp.zeros(n_experts, jnp.float32)
                                   for name in expert_layers}

    def step(p, state, batch):
        opt_state, biases = state
        (value, counts), grads = jax.value_and_grad(loss, has_aux=True)(
            p, biases, *batch)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        biases = {name: b + rate * jnp.sign(
            counts[name].mean() - counts[name]) for name, b in biases.items()}
        return optax.apply_updates(p, updates), (opt_state, biases), value

    return types.SimpleNamespace(
        conv=conv, attention=attention, experts=experts, logits=logits,
        loss=loss, init=init, step=step, expert_layers=expert_layers)


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    ref = build(config)
    return jax.jit(ref.init), jax.jit(ref.step, donate_argnums=(0, 1))
