"""Plain reference for the BERT family: forward pass, masked-LM loss,
gradients and update in straightforward ``jax.numpy`` and float32, matrix
multiplications at ``highest`` precision. No flax module, no kernel, nothing
of the library under test; the flax parameter tree is read by name.

Follows google-research/bert ``modeling.py`` (post-LayerNorm encoder, tanh
GELU, MLM head = dense + GELU + LayerNorm + tied embedding). Departures, all
the model's (configs/bert-large.json "assumed"): no token-type embedding, no
next-sentence head, no bias on the logits, no padding mask (every sequence
is full). The layers run under ``lax.scan`` over stacked weights with a
checkpoint per layer, so the program compiles in seconds and fits beside
nothing else; the batch is split over the cell's devices by ``jax.jit``'s
own shardings, which leaves the mathematics that of one global batch.
"""

import math

_LAYER_LEAVES = [f"{w}/{p}" for w in ("wq", "wk", "wv", "wo", "ffn_in",
                                      "ffn_out") for p in ("kernel", "bias")] \
    + [f"{n}/{p}" for n in ("attn_norm", "ffn_norm") for p in ("scale", "bias")]


def program(config, devices):
    """``init(params) -> state`` and the jitted ``step(params, state,
    batch) -> (params, state, loss)``. Trace the step under
    ``jax.default_matmul_precision("highest")``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    m = config["model"]
    n_layers, n_heads = m["num_hidden_layers"], m["num_attention_heads"]
    eps = m["layer_norm_eps"]
    opt = dict(config["optimizer"])
    optimizer = getattr(optax, opt.pop("name"))(**opt)

    def norm(x, p, name):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + eps) * p[name + "/scale"] \
            + p[name + "/bias"]

    def gelu(x):
        return 0.5 * x * (1 + jnp.tanh(
            math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))

    def dense(x, p, name):
        return x @ p[name + "/kernel"] + p[name + "/bias"]

    def layer(x, w):
        b, t, d = x.shape
        heads = lambda y: y.reshape(b, t, n_heads, d // n_heads)
        q, k, v = (heads(dense(x, w, n)) for n in ("wq", "wk", "wv"))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d // n_heads)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = norm(x + dense(o.reshape(b, t, d), w, "wo"), w, "attn_norm")
        f = dense(gelu(dense(x, w, "ffn_in")), w, "ffn_out")
        return norm(x + f, w, "ffn_norm"), None

    def loss_of(p, tokens, labels):
        x = p["tok_embedding"][tokens] \
            + p["pos_embedding"][None, :tokens.shape[1]]
        x = norm(x, p, "embed_norm")
        stacked = {leaf: jnp.stack([p[f"layer_{i}/{leaf}"]
                                    for i in range(n_layers)])
                   for leaf in _LAYER_LEAVES}
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
        h = norm(gelu(dense(x, p, "mlm_transform")), p, "mlm_norm")
        logits = h @ p["tok_embedding"].T
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        valid = labels >= 0
        return (nll * valid).sum() / valid.sum()

    def step(p, state, batch):
        loss, grads = jax.value_and_grad(loss_of)(p, *batch)
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state, loss

    mesh = Mesh(np.array(devices), ("batch",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("batch"))
    return jax.jit(optimizer.init, out_shardings=whole), jax.jit(
        step, in_shardings=(whole, whole, split), out_shardings=whole,
        donate_argnums=(0, 1))
