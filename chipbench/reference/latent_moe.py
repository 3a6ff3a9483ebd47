"""Plain reference for the latent-attention / routed-experts family: forward
pass, the two cross-entropy losses, gradients, AdamW and the router's bias
update in straightforward ``jax.numpy`` and float32, matrix multiplications
at ``highest`` precision. No flax module, no kernel, no sort, no grouped
product, nothing of the library under test; the flax parameter tree is read
by name.

Follows the equations of the published ``config.json``'s keys (DeepSeek-V3's
layer family, arXiv:2412.19437): latent attention with one rotary key vector
a position (adjacent pairs rotated), materialised causal softmax in query
blocks; layer 0 dense SwiGLU, every later layer sigmoid scores over ALL
experts, the top k of score + bias, gates renormalised over the chosen and
scaled, a loop over the HELD experts in which every token goes through every
expert under a gate that is 0 unless chosen (what the absent experts would
add is left out, as in the program), plus the shared expert;
one multi-token-prediction module on the trunk's last hidden state and the
next token's embedding; after the update, each bias moves by the sign of
(mean count - count) over the GLOBAL batch. Departures are the
configuration's (configs/joyai-llm-flash.json "assumed").

To fit beside 16 bytes a parameter of its own state, every layer maps over
the sequences one at a time with a ``jax.checkpoint`` a sequence (its
weights' gradients then accumulate over sequences inside the layer's own
backward pass), attention runs in checkpointed query blocks, and the logits
of one sequence at a time exist. Everything runs on the first device.
"""

import math
import types


def build(config):
    """The family's mathematics for ``config``: ``logits``, ``loss``, one
    expert layer (``experts``) and the un-jitted ``init`` and ``step``."""
    import jax
    import jax.numpy as jnp
    import optax

    c, a = config, config["assumed"]
    n_layers, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    H, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    rank, eps, theta = c["kv_lora_rank"], c["rms_norm_eps"], c["rope_theta"]
    held, first = c["n_routed_experts"], config["deployment"]["first_expert"]
    n_experts = config["published"]["n_routed_experts"]
    top_k, scaling = c["num_experts_per_tok"], c["routed_scaling_factor"]
    n_next = c["num_nextn_predict_layers"]
    gamma, lam = a["bias_update_rate"], a["mtp_loss_weight"]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)
    expert_layers = [f"layer_{i}" for i in range(n_dense, n_layers)] \
        + ["mtp_block"] * n_next

    def rms(x, scale):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * scale

    def rope(x, positions):
        """[T, heads, d]: rotate the adjacent pairs (2i, 2i+1)."""
        half = x.shape[-1] // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = positions[:, None, None].astype(jnp.float32) * freqs
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                          odd * jnp.cos(ang) + even * jnp.sin(ang)],
                         -1).reshape(x.shape)

    def attention(x, p, pre):
        T = x.shape[0]
        pos = jnp.arange(T)
        q = rms(x @ p[pre + "wq_a/kernel"], p[pre + "q_norm/scale"]) \
            @ p[pre + "wq_b/kernel"]
        q = q.reshape(T, H, dn + dr)
        kva = x @ p[pre + "wkv_a/kernel"]
        kv = rms(kva[:, :rank], p[pre + "kv_norm/scale"]) \
            @ p[pre + "wkv_b/kernel"]
        kv = kv.reshape(T, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos)], -1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            rope(kva[:, None, rank:], pos), (T, H, dr))], -1)
        v = kv[..., dn:]
        bq = min(T, 512)

        @jax.checkpoint
        def block(args):
            qb, pb = args
            s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dn + dr)
            s = jnp.where(pb[None, :, None] >= pos[None, None, :], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, (q.reshape(T // bq, bq, H, dn + dr),
                                pos.reshape(T // bq, bq)))
        return o.reshape(T, H * dv) @ p[pre + "wo/kernel"]

    def swiglu(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def experts(x, p, pre, bias):
        """``(sum of the held experts' gated outputs + the shared expert,
        tokens that chose each expert)``."""
        s = jax.nn.sigmoid(x @ p[pre + "router/kernel"])
        _, choice = jax.lax.top_k(s + bias, top_k)
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

    def layer(x, p, name, bias):
        """One block over every sequence, one sequence at a time."""
        pre = name + "/"

        def one(x):
            x = x + attention(rms(x, p[pre + "attn_norm/scale"]), p,
                              pre + "attn/")
            h = rms(x, p[pre + "mlp_norm/scale"])
            if bias is None:
                return x + swiglu(h, *(p[f"{pre}mlp/{w}/kernel"]
                                       for w in ("w1", "w3", "w2"))), 0.0
            y, counts = experts(h, p, pre + "moe/", bias)
            return x + y, counts

        # checkpointed inside the map: what the backward pass keeps of a
        # sequence is its input alone
        x, counts = jax.lax.map(jax.checkpoint(one), x)
        return x, jnp.sum(counts, 0)

    def trunk_and_module(p, biases, tokens):
        """Hidden states ``[B, S, D]`` that feed the shared head (the
        trunk's and, with a module, the module's) and the counts of every
        expert layer."""
        S = tokens.shape[1] - n_next
        embedded = p["embedding"][tokens]
        x, counts = embedded[:, :S], {}
        for i in range(n_layers):
            name = f"layer_{i}"
            x, n = layer(x, p, name, biases.get(name))
            if name in biases:
                counts[name] = n
        out = [rms(x, p["final_norm/scale"])]
        if n_next:
            u = jnp.concatenate(
                [rms(embedded[:, 1:], p["mtp_embed_norm/scale"]),
                 rms(x, p["mtp_hidden_norm/scale"])], -1) \
                @ p["mtp_proj/kernel"]
            u, counts["mtp_block"] = layer(u, p, "mtp_block",
                                           biases["mtp_block"])
            out.append(rms(u, p["mtp_final_norm/scale"]))
        return out, counts

    def logits(p, biases, tokens):
        hidden, _ = trunk_and_module(p, biases, tokens)
        return [h @ p["lm_head"] for h in hidden]

    def loss(p, biases, tokens, labels):
        hidden, counts = trunk_and_module(p, biases, tokens)

        @jax.checkpoint
        def ce(args):
            h, targets = args
            z = h @ p["lm_head"]
            return (jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
                z, targets[:, None], -1)[:, 0]).mean()

        S = hidden[0].shape[1]
        total = jax.lax.map(ce, (hidden[0], labels[:, :S])).mean()
        if n_next:
            total = total + lam * jax.lax.map(
                ce, (hidden[1], labels[:, 1:])).mean()
        return total, counts

    def init(p):
        return optimizer.init(p), {name: jnp.zeros(n_experts, jnp.float32)
                                   for name in expert_layers}

    def step(p, state, batch):
        opt_state, biases = state
        (value, counts), grads = jax.value_and_grad(loss, has_aux=True)(
            p, biases, *batch)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        biases = {name: b + gamma * jnp.sign(
            counts[name].mean() - counts[name]) for name, b in biases.items()}
        return optax.apply_updates(p, updates), (opt_state, biases), value

    return types.SimpleNamespace(logits=logits, loss=loss, init=init,
                                 step=step, experts=experts,
                                 expert_layers=expert_layers)


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    ref = build(config)
    return jax.jit(ref.init), jax.jit(ref.step, donate_argnums=(0, 1))
