"""Next-token pre-training of a decoder of gated short-convolution and
grouped-query attention layers with routed experts
(``horovod_tpu.models.conv_moe``), as ONE rank's share of each layer: its
experts and its slice of the vocabulary.

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step REQUIRES (computed from
shapes, so a share of peak cannot be moved by changing the program): the
causal triangle for attention, the routed rows at their expectation, the
head; nothing recomputed, and the convolution's taps and gates (elementwise,
no matrix product) not counted.
"""

import types

# keys of the published config.json that the model's config takes as they are
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
    "num_dense_layers", "conv_L_cache", "conv_bias", "num_experts_per_tok",
    "use_expert_bias", "norm_topk_prob", "routed_scaling_factor",
    "rope_theta", "norm_eps")


def model_config(config):
    """``models/conv_moe.py``'s configuration of a configuration file: the
    published count of experts, with this chip's share beside it."""
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.models.conv_moe import ConvMoEConfig

    c, a = config, config["assumed"]
    return ConvMoEConfig(
        **{key: c[key] for key in MODEL_KEYS},
        layer_types=tuple(c["layer_types"]),
        num_experts=config["published"]["num_experts"],
        experts_held=c["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        bias_update_rate=a["bias_update_rate"],
        dtype=jnp.dtype(a["compute_dtype"]), remat_policy=a["remat_policy"],
        axis_name=hvd.RANK_AXIS)


def build(config, traffic):
    import numpy as np
    import optax
    from horovod_tpu.models.conv_moe import ConvMoE
    from horovod_tpu.models.latent_moe import next_tokens_loss

    c = config
    seq, batch = traffic["seq"], traffic["global_batch"]
    if seq > c["max_position_embeddings"]:
        raise SystemExit(f"chipbench: seq {seq} exceeds the model's positions")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise SystemExit("chipbench: layer_types is not num_hidden_layers "
                         "long")

    def pool(seed):
        """``pool_batches`` global batches of ``seq + 1`` ids a sequence,
        uniform over the vocabulary slice: the model reads all but the
        last, the labels are the same ids one to the right."""
        rng = np.random.RandomState(seed % 2 ** 32)
        ids = rng.randint(0, c["vocab_size"],
                          (traffic["pool_batches"] * batch,
                           seq + 1)).astype(np.int32)
        return ids[:, :-1], ids[:, 1:]

    opt = dict(config["optimizer"])
    return types.SimpleNamespace(
        model=ConvMoE(model_config(config)), loss_fn=next_tokens_loss(),
        pool=pool, optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * batch * forward_flops_per_sequence(config, seq),
        flash=flash_work(config, seq, batch) if seq >= 512 else None)


def forward_flops_per_sequence(config, seq):
    """Matrix-multiply operations the forward pass requires for one sequence
    (2 per multiply-add); the backward pass requires twice that again."""
    c = config
    D, H, KV = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    d = D // H
    n_experts = config["published"]["num_experts"]
    expert = 3 * D * c["moe_intermediate_size"]
    total = 2 * seq * D * c["vocab_size"]                   # the head
    for layer, kind in enumerate(c["layer_types"]):
        if kind == "conv":                  # W_in (3D wide) and W_out
            total += 2 * seq * 4 * D * D
        else:       # wq and wo, wk and wv; QK^T and PV over the triangle
            total += 2 * seq * D * (2 * H * d + 2 * KV * d) \
                + 2 * H * seq * (seq + 1) // 2 * 2 * d
        if layer < c["num_dense_layers"]:
            total += 2 * seq * 3 * D * c["intermediate_size"]
        else:                   # the router, and the held experts' rows
            total += 2 * seq * (D * n_experts + c["num_experts_per_tok"]
                                * c["num_experts"] / n_experts * expert)
    return total


def flash_work(config, seq, batch):
    """Operations and bytes one training step requires of the attention
    kernels: with P = seq (seq + 1) / 2 visible pairs a sequence and head
    (query heads, after the KV heads are repeated) and depth d, forward
    QK^T and PV are 2 P x 2 d, backward the score recomputation, dQ, dK, dP
    and dV 2 P x 5 d; q, k, v, o read or written once forward, and q, k, v,
    o, dO, dQ, dK, dV once backward, in the compute type's 2 bytes."""
    c = config
    H = c["num_attention_heads"]
    d = c["hidden_size"] // H
    layers = c["layer_types"].count("full_attention")
    pairs = seq * (seq + 1) // 2
    return {"flops": layers * batch * H * 2 * pairs * 7 * d,
            "bytes": layers * batch * H * seq * 12 * d * 2}
