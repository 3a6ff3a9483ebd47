"""Next-token pre-training of a decoder of Kimi delta attention and latent
attention layers with group-routed experts
(``horovod_tpu.models.delta_moe``), as ONE rank's share of each layer: its
layers, its experts and its slice of the vocabulary.

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step REQUIRES (computed from
shapes, so a share of peak cannot be moved by changing the program): the
projections, the causal triangle for latent attention, the chunked delta
rule at a fixed chunk (``chipbench/delta_work.py``), the routed rows at
their expectation, the head; nothing recomputed, and the short
convolution's taps, the gates and the norms (elementwise) not counted.
"""

import types

from chipbench.delta_work import delta_rule_work

# keys of the published config.json that the model's config takes as they are
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "num_hidden_layers",
    "first_k_dense_replace", "layer_group_size", "num_attention_heads",
    "head_dim", "num_kv_heads_for_linear_attn", "short_conv_kernel_size",
    "kda_lower_bound", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
    "use_qk_norm", "gated_attention_proj_granularity_type",
    "num_experts_per_tok", "num_shared_experts", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor",
    "moe_router_enable_expert_bias")
# published keys the model does not take, at the one value it implements
IMPLEMENTED = {
    "hidden_act": "silu", "linear_silu": True, "kda_safe_gate": True,
    "no_kda_lora": True, "use_kda_lora": False, "group_norm_size": 1,
    "use_bias": False, "use_qkv_bias": False, "use_mla_nope": False,
    "use_nGPT": False, "up_proj_norm": False, "value_norm": False,
    "scale_router_input": False, "scoring_func": "sigmoid",
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "tie_word_embeddings": False, "rope_interleave": True,
    "rope_scaling": None, "num_nextn_predict_layers": 0}


def layer_kinds(config):
    """``kda`` or ``mla`` of each layer held, by its published index."""
    group = config["layer_group_size"]
    return ["mla" if (index + 1) % group == 0 else "kda"
            for index in config["deployment"]["layers"]]


def model_config(config):
    """``models/delta_moe.py``'s configuration of a configuration file: the
    published count of experts, with this chip's share beside it."""
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.models.delta_moe import DeltaMoEConfig

    c, a, deployment = config, config["assumed"], config["deployment"]
    return DeltaMoEConfig(
        **{key: c[key] for key in MODEL_KEYS},
        num_experts=config["published"]["num_experts"],
        experts_held=c["num_experts"],
        first_expert=deployment["first_expert"],
        layer_indices=tuple(deployment["layers"]),
        bias_update_rate=a["bias_update_rate"],
        dtype=jnp.dtype(a["compute_dtype"]), remat_policy=a["remat_policy"],
        axis_name=hvd.RANK_AXIS)


def check(config):
    """Refuse a configuration the model would compute otherwise than
    published: a key at a value not implemented, or a held layer with a
    SwiGLU limit."""
    for key, value in IMPLEMENTED.items():
        if config[key] != value:
            raise SystemExit(f"chipbench: {key} = {config[key]!r} is not "
                             f"implemented (only {value!r})")
    layers = config["deployment"]["layers"]
    if len(layers) != config["num_hidden_layers"]:
        raise SystemExit("chipbench: deployment.layers is not "
                         "num_hidden_layers long")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(config[key][index] for index in layers):
            raise SystemExit(f"chipbench: a held layer has a {key} entry; "
                             "SwiGLU limits are not implemented")


def build(config, traffic):
    import numpy as np
    import optax
    from horovod_tpu.models.delta_moe import DeltaMoE
    from horovod_tpu.models.latent_moe import next_tokens_loss

    c = config
    seq, batch = traffic["seq"], traffic["global_batch"]
    if seq > c["max_position_embeddings"]:
        raise SystemExit(f"chipbench: seq {seq} exceeds the model's positions")
    check(config)

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
    kda = kda_scan_work(config, seq, batch)
    return types.SimpleNamespace(
        model=DeltaMoE(model_config(config)), loss_fn=next_tokens_loss(),
        pool=pool, optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * batch * forward_flops_per_sequence(config, seq)
        + kda["flops"],
        flash=flash_work(config, seq, batch) if seq >= 512 else None,
        kda_scan=kda)


def forward_flops_per_sequence(config, seq):
    """Matrix-multiply operations the forward pass requires for one sequence
    (2 per multiply-add) outside the delta rule; the backward pass requires
    twice that again."""
    c = config
    D, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dn, dv, rank = c["qk_nope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    n_experts = config["published"]["num_experts"]
    expert = 3 * D * c["moe_intermediate_size"]
    total = 2 * seq * D * c["vocab_size"]                   # the head
    for layer, kind in enumerate(layer_kinds(config)):
        if kind == "kda":       # [W_q | W_k | W_v], W_f, W_g, W_o; W_b
            total += 2 * seq * D * (6 * H * c["head_dim"] + H)
        else:   # wq, wkv_a, wkv_b, wo, the gate; QK^T and PV, causal
            total += 2 * seq * (D * H * qk + D * (rank + c["qk_rope_head_dim"])
                                + rank * H * (dn + dv) + H * dv * D + D * H) \
                + 2 * H * seq * (seq + 1) // 2 * (qk + dv)
        if layer < c["first_k_dense_replace"]:
            total += 2 * seq * 3 * D * c["intermediate_size"]
        else:   # the router, the shared expert, the held experts' rows
            shared = 3 * D * c["moe_shared_expert_intermediate_size"]
            total += 2 * seq * (
                D * n_experts + shared + c["num_experts_per_tok"]
                * c["num_experts"] / n_experts * expert)
    return total


def kda_scan_work(config, seq, batch):
    """What a step requires of the delta rule, over every delta layer."""
    return delta_rule_work(seq, batch, config["num_attention_heads"],
                           config["head_dim"], config["head_dim"],
                           layer_kinds(config).count("kda"))


def flash_work(config, seq, batch):
    """Operations and bytes one training step requires of the attention
    kernels, over the latent-attention layers: with P visible pairs a
    sequence and head, forward QK^T and PV are 2 P (dqk + dv), backward the
    score recomputation, dQ and dK (3 x dqk) and dP and dV (2 x dv); q, k
    (dqk deep) and v, o (dv deep) read or written once forward, and q, k, v,
    o, dO, dQ, dK, dV once backward, in the compute type's 2 bytes."""
    c = config
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dv = c["v_head_dim"]
    rows = batch * c["num_attention_heads"] * layer_kinds(config).count("mla")
    pairs = seq * (seq + 1) // 2
    return {"flops": rows * 2 * pairs * (4 * qk + 3 * dv),
            "bytes": rows * seq * (6 * qk + 6 * dv) * 2}
