"""Plain reference for the Kimi-delta / latent attention, group-routed experts
family: forward pass, next-token cross entropy, gradients, AdamW and the
router's bias update in straightforward ``jax.numpy`` and float32, matrix
multiplications at ``highest`` precision. No flax module, no kernel, no
chunked form, no sort, no grouped product, nothing of the library under
test; the flax parameter tree is read by name.

The equations (configs/ling-3.0-flash.json "assumed"), for one sequence
``x [T, D]`` and ``h = RMSNorm(x)``; layer ``l`` (its published index) is
latent attention when ``(l + 1) % layer_group_size == 0``, else delta:

- delta layer, ``H`` heads of ``d``: ``[q | k | v] = SiLU(conv(h [W_q | W_k
  | W_v]))`` with ``conv`` depthwise and causal over ``L`` taps (``z_t =
  sum_j w_j y_{t - (L - 1) + j}``, zero before position 0); ``q <- q / (|q|
  sqrt(d))``, ``k <- k / |k|`` a head; ``g = lower * sigmoid(exp(A_log) *
  (h W_f + dt_bias))``, ``beta = sigmoid(h W_b)``; ONE POSITION AT A TIME
  from ``S = 0``: ``S <- (I - beta k k^T) Diag(e^g) S + beta k v^T``, ``o =
  S^T q``; ``y = x + (RMSNorm_head(o) * sigmoid(h W_g)) W_o``;
- latent layer: ``q = RMSNorm_head(h W_q)`` (192 deep), ``[c | k_r] = h
  W_kv_a``, ``[k_n | v] = RMSNorm(c) W_kv_b`` a head, ``k =
  RMSNorm_head([k_n ; k_r])``; adjacent pairs of the last 64 depths of q
  and k rotated, ``theta ** (-2i / 64)``; softmax of ``q . k / sqrt(192)``
  over keys ``j <= i``; each head's output times ``sigmoid(h W_gate)``;
  ``y = x + o W_o``;
- ``b = RMSNorm(y)``; below ``first_k_dense_replace`` ``z = y + W2
  (silu(W1 b) * W3 b)``; above it ``s = sigmoid(b Wr)`` over ALL experts,
  ``n_group`` groups of consecutive experts scored by the sum of their two
  largest ``s + bias``, the experts of the ``topk_group`` best groups
  alone eligible, the ``num_experts_per_tok`` largest ``s + bias`` among
  them chosen, ``gamma_i = scaling * s_i / sum_chosen s``, ``z = y +
  shared(b) + sum over chosen HELD experts of gamma_i E_i(b)``: every token
  through every held expert under a gate that is 0 unless chosen;
- ``logits = RMSNorm(z_last) W_head``; the mean next-token cross entropy;
  after the update each bias moves by ``rate * sign(mean count - count)``
  over the global batch.

Departures, memory only and the same arithmetic, so that float32 at 4,096
tokens fits beside 16 bytes a parameter of its own state: every layer maps
over the sequences one at a time with a ``jax.checkpoint`` a sequence, the
recurrence is checkpointed every 64 positions, attention materialises the
scores of one block of 512 queries at a time (checkpointed), the held
experts are scanned (checkpointed), and logits exist 1,024 positions at a
time inside a checkpointed cross entropy. Everything runs on the first
device.
"""

import math
import types

POSITIONS_A_CHECKPOINT = 64


def build(config):
    """The family's mathematics for ``config``: ``delta`` (one delta layer's
    mixer, before the residual), ``recurrence``, ``attention``, ``experts``
    (one sparse layer's feed-forward part and its counts), ``logits``,
    ``loss`` and the un-jitted ``init`` and ``step``."""
    import jax
    import jax.numpy as jnp
    import optax

    c, a = config, config["assumed"]
    D, H, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    rank, eps, lower = c["kv_lora_rank"], c["rms_norm_eps"], \
        c["kda_lower_bound"]
    indices, group = config["deployment"]["layers"], c["layer_group_size"]
    kinds = ["mla" if (i + 1) % group == 0 else "kda" for i in indices]
    n_dense = c["first_k_dense_replace"]
    held, first = c["num_experts"], config["deployment"]["first_expert"]
    n_experts = config["published"]["num_experts"]
    top_k, scaling = c["num_experts_per_tok"], c["routed_scaling_factor"]
    n_group, topk_group = c["n_group"], c["topk_group"]
    rate = a["bias_update_rate"] if c["moe_router_enable_expert_bias"] \
        else 0.0
    expert_layers = [f"layer_{i}" for i in range(n_dense, len(kinds))]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    def recurrence(q, k, v, g, beta):
        """``o [T, H, d]`` of the delta rule one position at a time."""
        def one(S, inputs):
            q, k, v, g, beta = inputs             # [H, d] x4, [H]
            S = jnp.exp(g)[:, :, None] * S        # Diag(alpha) S
            S = S - beta[:, None, None] * k[:, :, None] \
                * jnp.einsum("hd,hde->he", k, S)[:, None, :] \
                + beta[:, None, None] * k[:, :, None] * v[:, None, :]
            return S, jnp.einsum("hde,hd->he", S, q)

        @jax.checkpoint
        def span(S, inputs):
            return jax.lax.scan(one, S, inputs)

        T = q.shape[0]
        n = math.gcd(T, POSITIONS_A_CHECKPOINT)
        inputs = tuple(x.reshape(T // n, n, *x.shape[1:])
                       for x in (q, k, v, g, beta))
        _, o = jax.lax.scan(span, jnp.zeros((H, d, d), jnp.float32), inputs)
        return o.reshape(T, H, d)

    def delta(x, p, pre):
        """``(RMSNorm_head(o) * sigmoid(x W_g)) W_o`` of one sequence ``x
        [T, D]`` (normed)."""
        T = x.shape[0]
        y = x @ p[pre + "qkv_proj/kernel"]                # [T, 3 H d]
        taps = p[pre + "conv"]                            # [L, 3 H d]
        L = taps.shape[0]
        z = jnp.zeros_like(y)
        for j in range(L):      # tap j reads y_{t - (L - 1 - j)}
            shift = L - 1 - j
            z = z + taps[j] * jnp.concatenate(
                [jnp.zeros((shift, y.shape[1]), y.dtype), y[:T - shift]])
        q, k, v = jnp.split(jax.nn.silu(z).reshape(T, 3 * H, d), 3, axis=1)
        q, k = unit(q) / math.sqrt(d), unit(k)
        g = lower * jax.nn.sigmoid(
            jnp.exp(p[pre + "A_log"])[:, None]
            * (x @ p[pre + "f_proj/kernel"] + p[pre + "dt_bias"]).reshape(
                T, H, d))
        beta = jax.nn.sigmoid(x @ p[pre + "b_proj/kernel"])
        o = rms(recurrence(q, k, v, g, beta), p[pre + "o_norm/scale"])
        o = o * jax.nn.sigmoid(x @ p[pre + "g_proj/kernel"]).reshape(T, H, d)
        return o.reshape(T, H * d) @ p[pre + "o_proj/kernel"]

    def rope(x, positions):
        """[T, heads, dr]: rotate the adjacent pairs (2i, 2i+1)."""
        half = x.shape[-1] // 2
        freqs = c["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32)
                                    / half)
        ang = positions[:, None, None].astype(jnp.float32) * freqs
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                          odd * jnp.cos(ang) + even * jnp.sin(ang)],
                         -1).reshape(x.shape)

    def attention(x, p, pre):
        """``o W_o`` of one sequence ``x [T, D]`` (normed)."""
        T = x.shape[0]
        pos = jnp.arange(T)
        q = rms((x @ p[pre + "wq/kernel"]).reshape(T, H, dn + dr),
                p[pre + "q_head_norm/scale"])
        kva = x @ p[pre + "wkv_a/kernel"]
        kv = (rms(kva[:, :rank], p[pre + "kv_norm/scale"])
              @ p[pre + "wkv_b/kernel"]).reshape(T, H, dn + dv)
        k = rms(jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            kva[:, None, rank:], (T, H, dr))], -1),
            p[pre + "k_head_norm/scale"])
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos)], -1)
        k = jnp.concatenate([k[..., :dn], rope(k[..., dn:], pos)], -1)
        v = kv[..., dn:]
        bq = math.gcd(T, 512)

        @jax.checkpoint
        def block(args):
            qb, pb = args
            s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dn + dr)
            s = jnp.where(pb[None, :, None] >= pos[None, None, :], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, (q.reshape(T // bq, bq, H, dn + dr),
                                pos.reshape(T // bq, bq))).reshape(T, H, dv)
        o = o * jax.nn.sigmoid(x @ p[pre + "wg/kernel"])[:, :, None]
        return o.reshape(T, H * dv) @ p[pre + "wo/kernel"]

    def swiglu(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def experts(x, p, pre, bias):
        """``(the shared expert plus the held experts' gated outputs, tokens
        that chose each expert)``."""
        s = jax.nn.sigmoid(x @ p[pre + "router/kernel"])
        biased = (s + bias).reshape(-1, n_group, n_experts // n_group)
        best_two = jnp.sort(biased, -1)[..., -2:].sum(-1)       # [T, groups]
        _, kept = jax.lax.top_k(best_two, topk_group)
        eligible = jax.nn.one_hot(kept, n_group).sum(1)          # [T, groups]
        biased = jnp.where(eligible[..., None] > 0, biased, -jnp.inf)
        _, choice = jax.lax.top_k(biased.reshape(-1, n_experts), top_k)
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
        return y, chosen.sum(0)

    def layer(x, p, i, bias):
        """One block over every sequence, one sequence at a time, and the
        tokens that chose each expert (0 in a dense layer)."""
        pre = f"layer_{i}/"

        def one(x):
            h = rms(x, p[pre + "attn_norm/scale"])
            if kinds[i] == "kda":
                x = x + delta(h, p, pre + "attn/")
            else:
                x = x + attention(h, p, pre + "attn/")
            h = rms(x, p[pre + "mlp_norm/scale"])
            if bias is None:
                return x + swiglu(h, *(p[f"{pre}mlp/{w}/kernel"]
                                       for w in ("w1", "w3", "w2"))), 0.0
            y, counts = experts(h, p, pre + "moe/", bias)
            return x + y, counts

        x, counts = jax.lax.map(jax.checkpoint(one), x)
        return x, jnp.sum(counts, 0)

    def hidden(p, biases, tokens):
        x, counts = p["embedding"][tokens], {}
        for i in range(len(kinds)):
            name = f"layer_{i}"
            x, counts[name] = layer(x, p, i, biases.get(name))
        return rms(x, p["final_norm/scale"]), counts

    def logits(p, biases, tokens):
        return hidden(p, biases, tokens)[0] @ p["lm_head"]

    def loss(p, biases, tokens, labels):
        h, counts = hidden(p, biases, tokens)

        @jax.checkpoint
        def ce(block):
            hb, targets = block                     # [n, D], [n]
            z = hb @ p["lm_head"]
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
        delta=delta, recurrence=recurrence, attention=attention,
        experts=experts, logits=logits, loss=loss, init=init, step=step,
        expert_layers=expert_layers)


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    ref = build(config)
    return jax.jit(ref.init), jax.jit(ref.step, donate_argnums=(0, 1))
