"""Plain reference for the window/full attention, routed-experts family:
forward pass, next-token cross entropy, gradients and AdamW in
straightforward ``jax.numpy`` and float32, matrix multiplications at
``highest`` precision. No flax module, no kernel, no sort, no grouped
product, nothing of the library under test; the flax parameter tree is read
by name.

The equations (ISSUE 35 and configs/laguna-s-2.1.json "assumed"), for a
layer of kind ``full_attention`` or ``sliding_attention`` with ``H`` query
heads and ``KV`` KV heads of depth ``d`` held here, x ``[T, D]``:

- ``a = RMSNorm(x)``; ``q = a Wq`` ``[T, H, d]``, ``k = a Wk``, ``v = a Wv``
  ``[T, KV, d]``, ``g = sigmoid(a Wg)`` ``[T, H]``;
- rotary on half-split pairs. Sliding: every depth, ``theta ** (-2i/d)``.
  Full: the first ``d * partial_rotary_factor`` depths by YaRN's frequencies
  (the plain one where the pair turns more than ``beta_fast`` times over the
  original positions, the plain one over ``factor`` below ``beta_slow``
  turns, a linear ramp between), cos and sin times ``attention_factor``; the
  other depths pass unrotated;
- query head ``h`` reads KV head ``h // (H / KV)``; scores ``q_i . k_j /
  sqrt(d)``; key ``j`` visible to query ``i`` iff ``j <= i`` (full) or
  ``i - sliding_window < j <= i`` (sliding); ``o_h = softmax(s) v``;
- ``y = x + concat_h(g_h o_h) Wo``; ``b = RMSNorm(y)``;
- dense layer: ``z = y + W2 (silu(W1 b) * W3 b)``; sparse layer: ``p =
  sigmoid(b Wr)`` over ALL experts, the ``num_experts_per_tok`` largest
  chosen, ``gamma_i = scaling * p_i / sum_chosen p``, ``z = y + Shared(b) +
  sum over chosen HELD experts of gamma_i E_i(b)``: every token through every
  held expert under a gate that is 0 unless chosen;
- ``logits = RMSNorm(z_last) W_head``; the mean next-token cross entropy.

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


def inv_freq(scheme, head_dim):
    """Rotary frequencies of one ``rope_parameters`` entry, as
    ``transformers`` computes them (``_compute_yarn_parameters`` for
    ``rope_type`` "yarn", the plain ones otherwise), as Python floats."""
    dim = int(head_dim * scheme.get("partial_rotary_factor", 1))
    base = scheme["rope_theta"]
    pos_freqs = [base ** (2 * i / dim) for i in range(dim // 2)]
    if scheme.get("rope_type", "default") != "yarn":
        return [1.0 / f for f in pos_freqs]
    factor = scheme["factor"]
    original = scheme["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(scheme["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scheme["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(pos_freqs):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        extrapolation = 1.0 - ramp
        out.append((1.0 / (factor * f)) * (1.0 - extrapolation)
                   + (1.0 / f) * extrapolation)
    return out


def build(config):
    """The family's mathematics for ``config``: ``attention`` (one layer's,
    before the residual), ``experts`` (one sparse layer's feed-forward
    part), ``logits``, ``loss`` and the un-jitted ``init`` and ``step``."""
    import jax
    import jax.numpy as jnp
    import optax

    c = config
    d, eps = c["head_dim"], c["rms_norm_eps"]
    KV = c["num_key_value_heads"]
    kinds, mlp_kinds = c["layer_types"], c["mlp_layer_types"]
    heads = c["num_attention_heads_per_layer"]
    window = c["sliding_window"]
    held = c["num_experts"]
    n_experts = config["published"]["num_experts"]
    first = config["deployment"]["first_expert"]
    top_k, scaling = c["num_experts_per_tok"], c["moe_routed_scaling_factor"]
    schemes = c["rope_parameters"]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    def rotary(x, positions, scheme):
        """[T, heads, d]: rotate the pairs (i, i + n) of the first 2n
        depths, n frequencies; the rest pass."""
        freqs = jnp.asarray(inv_freq(scheme, d), jnp.float32)
        n = freqs.shape[0]
        ang = positions[:, None, None].astype(jnp.float32) * freqs
        factor = scheme.get("attention_factor", 1.0) \
            if scheme.get("rope_type", "default") == "yarn" else 1.0
        cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
        x1, x2 = x[..., :n], x[..., n:2 * n]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * n:]], -1)

    def attention(x, p, pre, layer):
        """``concat_h(g_h o_h) Wo`` of one sequence ``x [T, D]`` (normed)."""
        T, H, kind = x.shape[0], heads[layer], kinds[layer]
        G = H // KV
        pos = jnp.arange(T)
        q = rotary((x @ p[pre + "wq/kernel"]).reshape(T, H, d), pos,
                   schemes[kind])
        k = rotary((x @ p[pre + "wk/kernel"]).reshape(T, KV, d), pos,
                   schemes[kind])
        v = (x @ p[pre + "wv/kernel"]).reshape(T, KV, d)
        gate = jax.nn.sigmoid(x @ p[pre + "wg/kernel"])          # [T, H]
        bq = math.gcd(T, 512)

        @jax.checkpoint
        def one_kv_head(args):
            qh, kh, vh = args               # [T, G, d], [T, d], [T, d]

            @jax.checkpoint
            def block(args):
                qb, pb = args               # [bq, G, d], [bq]
                s = jnp.einsum("qgd,kd->gqk", qb, kh) / math.sqrt(d)
                seen = pb[:, None] >= pos[None, :]
                if kind == "sliding_attention":
                    seen &= pb[:, None] - pos[None, :] < window
                s = jnp.where(seen[None], s, -jnp.inf)
                return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, -1), vh)

            return jax.lax.map(block, (qh.reshape(T // bq, bq, G, d),
                                       pos.reshape(T // bq, bq))
                               ).reshape(T, G, d)

        o = jax.lax.map(one_kv_head, (
            q.reshape(T, KV, G, d).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [KV, T, G, d]
        o = o.transpose(1, 0, 2, 3).reshape(T, H, d) * gate[..., None]
        return o.reshape(T, H * d) @ p[pre + "wo/kernel"]

    def swiglu(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def experts(x, p, pre):
        """The shared expert plus the held experts' gated outputs."""
        s = jax.nn.sigmoid(x @ p[pre + "router/kernel"])
        _, choice = jax.lax.top_k(s, top_k)
        chosen = jax.nn.one_hot(choice, n_experts).sum(1)        # [T, E] 0/1
        gates = scaling * s * chosen / (s * chosen).sum(-1, keepdims=True)
        y = swiglu(x, *(p[f"{pre}shared/{w}/kernel"]
                        for w in ("w1", "w3", "w2")))

        def add_expert(y, expert):      # every token through every expert,
            gate, w1, w3, w2 = expert   # weighed by a gate that is 0 unless
            return y + gate[:, None] * swiglu(x, w1, w3, w2), None   # chosen

        y, _ = jax.lax.scan(jax.checkpoint(add_expert), y, (
            gates[:, first:first + held].T, p[pre + "w1"], p[pre + "w3"],
            p[pre + "w2"]))
        return y

    def layer(x, p, i):
        """One block over every sequence, one sequence at a time."""
        pre = f"layer_{i}/"

        def one(x):
            x = x + attention(rms(x, p[pre + "attn_norm/scale"]), p,
                              pre + "attn/", i)
            h = rms(x, p[pre + "mlp_norm/scale"])
            if mlp_kinds[i] == "dense":
                return x + swiglu(h, *(p[f"{pre}mlp/{w}/kernel"]
                                       for w in ("w1", "w3", "w2")))
            return x + experts(h, p, pre + "moe/")

        return jax.lax.map(jax.checkpoint(one), x)

    def hidden(p, tokens):
        x = p["embedding"][tokens]
        for i in range(len(kinds)):
            x = layer(x, p, i)
        return rms(x, p["final_norm/scale"])

    def logits(p, tokens):
        return hidden(p, tokens) @ p["lm_head"]

    def loss(p, tokens, labels):
        h = hidden(p, tokens)

        @jax.checkpoint
        def ce(block):
            hb, targets = block                     # [n, D], [n]
            z = hb @ p["lm_head"]
            return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
                z, targets[:, None], -1)[:, 0]

        n = math.gcd(labels.size, 1024)             # positions a block
        return jax.lax.map(ce, (h.reshape(-1, n, h.shape[-1]),
                                labels.reshape(-1, n))).mean()

    def step(p, opt_state, batch):
        value, grads = jax.value_and_grad(loss)(p, *batch)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, value

    return types.SimpleNamespace(
        attention=attention, experts=experts, logits=logits, loss=loss,
        init=optimizer.init, step=step)


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    ref = build(config)
    return jax.jit(ref.init), jax.jit(ref.step, donate_argnums=(0, 1))
