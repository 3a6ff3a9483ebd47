"""Next-token pre-training of a latent-attention decoder with routed experts
and a multi-token-prediction module (``horovod_tpu.models.latent_moe``), as
ONE expert-parallel rank's share of each layer.

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step REQUIRES (computed from
shapes, so a share of peak cannot be moved by changing the program): the
causal triangle for attention, the routed rows at their expectation, both
heads; nothing recomputed.
"""

import types

# keys of the published config.json that the model's config takes as they are
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "rms_norm_eps", "n_shared_experts",
    "num_experts_per_tok", "routed_scaling_factor",
    "num_nextn_predict_layers")

def build(config, traffic):
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models.latent_moe import (LatentMoE, LatentMoEConfig,
                                               next_tokens_loss)

    c, a = config, config["assumed"]
    seq, batch = traffic["seq"], traffic["global_batch"]
    if seq > c["max_position_embeddings"]:
        raise SystemExit(f"chipbench: seq {seq} exceeds the model's positions")
    model = LatentMoE(LatentMoEConfig(
        **{key: c[key] for key in MODEL_KEYS},
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=c["n_routed_experts"],
        first_expert=config["deployment"]["first_expert"],
        bias_update_rate=a["bias_update_rate"],
        dtype=jnp.dtype(a["compute_dtype"]), remat_policy=a["remat_policy"],
        axis_name=hvd.RANK_AXIS))
    n_next = c["num_nextn_predict_layers"]

    def pool(seed):
        """``pool_batches`` global batches of ``seq + 1 + n_next`` ids a
        sequence, uniform over the vocabulary slice: the model reads all but
        the last, the labels are the same ids one to the right."""
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, c["vocab_size"],
                          (traffic["pool_batches"] * batch,
                           seq + 1 + n_next)).astype(np.int32)
        return ids[:, :-1], ids[:, 1:]

    opt = dict(config["optimizer"])
    return types.SimpleNamespace(
        model=model, loss_fn=next_tokens_loss(a["mtp_loss_weight"]),
        pool=pool, optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * batch * forward_flops_per_sequence(config, seq),
        flash=flash_work(config, seq, batch) if seq >= 512 else None)


def _attention_layers(c):
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def _visible_pairs(seq):
    """(query, key) pairs a causal mask leaves, a sequence and head."""
    return seq * (seq + 1) // 2


def forward_flops_per_sequence(config, seq):
    """Matrix-multiply operations the forward pass requires for one sequence
    (2 per multiply-add); the backward pass requires twice that again."""
    c = config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    nope_v = c["qk_nope_head_dim"] + c["v_head_dim"]
    projections = (d * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
                   + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                   + c["kv_lora_rank"] * heads * nope_v
                   + heads * c["v_head_dim"] * d)
    attention = 2 * seq * projections \
        + 2 * heads * _visible_pairs(seq) * (qk + c["v_head_dim"])
    expert = 3 * d * c["moe_intermediate_size"]
    held_share = c["n_routed_experts"] / config["published"]["n_routed_experts"]
    expert_layer = 2 * seq * (
        d * config["published"]["n_routed_experts"]           # router
        + c["n_shared_experts"] * expert
        + c["num_experts_per_tok"] * held_share * expert)     # expected rows
    dense_layers = c["first_k_dense_replace"]
    n_next = c["num_nextn_predict_layers"]
    return (_attention_layers(c) * attention
            + dense_layers * 2 * seq * 3 * d * c["intermediate_size"]
            + (c["num_hidden_layers"] - dense_layers + n_next) * expert_layer
            + n_next * 2 * seq * 2 * d * d                    # [e ; h] -> d
            + (1 + n_next) * 2 * seq * d * c["vocab_size"])   # shared head


def flash_work(config, seq, batch):
    """Operations and bytes one training step requires of the attention
    kernels, over every attention layer: with P visible pairs a sequence and
    head, forward QK^T and PV are 2 P (dqk + dv), backward the score
    recomputation, dQ and dK (3 x dqk) and dP and dV (2 x dv); q, k (dqk
    deep) and v, o (dv deep) read or written once forward, and q, k, v, o,
    dO, dQ, dK, dV once backward, in the compute type's 2 bytes."""
    c = config
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dv = c["v_head_dim"]
    rows = batch * c["num_attention_heads"] * _attention_layers(c)
    return {"flops": rows * 2 * _visible_pairs(seq) * (4 * qk + 3 * dv),
            "bytes": rows * seq * (6 * qk + 6 * dv) * 2}
