"""Next-token pre-training of a looped decoder (``horovod_tpu.models.looped``):
one stack of layers run ``total_ut_steps`` times over the same weights, a
head and a cross entropy after every pass, an exit gate that weighs them.

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step REQUIRES (computed from
shapes, so a share of peak cannot be moved by changing the program): every
pass of every layer, the causal triangle for attention, all the passes'
heads (the loss requires each) and gates; nothing recomputed.
"""

import types

# keys of the published config.json that the model's config takes as they are
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "rope_theta",
    "rms_norm_eps", "total_ut_steps")


def build(config, traffic):
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.models.looped import Looped, LoopedConfig, looped_loss

    c, a = config, config["assumed"]
    seq, batch = traffic["seq"], traffic["global_batch"]
    if seq > c["max_position_embeddings"]:
        raise SystemExit(f"chipbench: seq {seq} exceeds the model's positions")
    if c["head_dim"] * c["num_attention_heads"] != c["hidden_size"]:
        raise SystemExit("chipbench: the heads do not fill the hidden size")
    model = Looped(LoopedConfig(
        **{key: c[key] for key in MODEL_KEYS},
        dtype=jnp.dtype(a["compute_dtype"]), remat_policy=a["remat_policy"]))

    def pool(seed):
        """``pool_batches`` global batches of ``seq + 1`` ids a sequence,
        uniform over the vocabulary: the model reads all but the last, the
        labels are the same ids one to the right."""
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, c["vocab_size"],
                          (traffic["pool_batches"] * batch,
                           seq + 1)).astype(np.int32)
        return ids[:, :-1], ids[:, 1:]

    opt = dict(config["optimizer"])
    return types.SimpleNamespace(
        model=model, loss_fn=looped_loss(a["exit_entropy_weight"]),
        pool=pool, optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * batch * forward_flops_per_sequence(config, seq),
        flash=flash_work(config, seq, batch) if seq >= 512 else None)


def _visible_pairs(seq):
    """(query, key) pairs a causal mask leaves, a sequence and head."""
    return seq * (seq + 1) // 2


def forward_flops_per_sequence(config, seq):
    """Matrix-multiply operations the forward pass requires for one sequence
    (2 per multiply-add); the backward pass requires twice that again."""
    c = config
    d, passes = c["hidden_size"], c["total_ut_steps"]
    layer = 2 * seq * (4 * d * d + 3 * d * c["intermediate_size"]) \
        + 2 * c["num_attention_heads"] * _visible_pairs(seq) \
        * 2 * c["head_dim"]                                 # QK^T and PV
    return passes * (c["num_hidden_layers"] * layer
                     + 2 * seq * d * c["vocab_size"]        # a head a pass
                     + 2 * seq * d)                         # and a gate


def flash_work(config, seq, batch):
    """Operations and bytes one training step requires of the attention
    kernels, over every application of every layer: with P visible pairs a
    sequence and head and depth D, forward QK^T and PV are 2 P x 2 D,
    backward the score recomputation, dQ, dK, dP and dV 2 P x 5 D; q, k, v,
    o read or written once forward, and q, k, v, o, dO, dQ, dK, dV once
    backward, in the compute type's 2 bytes."""
    c = config
    rows = batch * c["num_attention_heads"] * c["num_hidden_layers"] \
        * c["total_ut_steps"]
    return {"flops": rows * 2 * _visible_pairs(seq) * 7 * c["head_dim"],
            "bytes": rows * seq * 12 * c["head_dim"] * 2}
