"""Next-token pre-training of a decoder of window and full attention layers
with a gate a head and routed experts (``horovod_tpu.models.window_moe``),
as ONE rank's share of each layer: its KV heads with their query heads, its
experts, its slice of the vocabulary.

``build(config, traffic)`` gives the harness the model, loss, optimizer and
seeded batch pool of one cell, and the work a step REQUIRES (computed from
shapes, so a share of peak cannot be moved by changing the program): the
visible pairs of each layer's mask for attention (the causal triangle of a
full layer, the band of a sliding one), the routed rows at their
expectation, the head; nothing recomputed.
"""

import types

# keys of the published config.json that the model's config takes as they are
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "shared_expert_intermediate_size", "head_dim",
    "sliding_window", "rms_norm_eps", "num_experts_per_tok",
    "moe_routed_scaling_factor")
LISTS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")


def model_config(config):
    """``models/window_moe.py``'s configuration of a configuration file:
    the published counts of KV heads, heads a layer and experts, with this
    chip's share of each beside them."""
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.models.window_moe import RopeScheme, WindowMoEConfig

    c, a, pub = config, config["assumed"], config["published"]
    groups = [n // pub["num_key_value_heads"]
              for n in pub["num_attention_heads_per_layer"]]
    held = [n // c["num_key_value_heads"]
            for n in c["num_attention_heads_per_layer"]]
    if held != groups[:len(held)]:
        raise SystemExit("chipbench: the heads held a KV head are not the "
                         "published ones")
    return WindowMoEConfig(
        **{key: c[key] for key in MODEL_KEYS},
        layer_types=tuple(c["layer_types"]),
        mlp_layer_types=tuple(c["mlp_layer_types"]),
        num_attention_heads_per_layer=tuple(
            pub["num_attention_heads_per_layer"][:c["num_hidden_layers"]]),
        num_key_value_heads=pub["num_key_value_heads"],
        kv_heads_held=c["num_key_value_heads"],
        num_experts=pub["num_experts"], experts_held=c["num_experts"],
        first_expert=config["deployment"]["first_expert"],
        rope_full=RopeScheme.from_published(
            c["rope_parameters"]["full_attention"]),
        rope_sliding=RopeScheme.from_published(
            c["rope_parameters"]["sliding_attention"]),
        dtype=jnp.dtype(a["compute_dtype"]), remat_policy=a["remat_policy"],
        axis_name=hvd.RANK_AXIS)


def build(config, traffic):
    import numpy as np
    import optax
    from horovod_tpu.models.latent_moe import next_tokens_loss
    from horovod_tpu.models.window_moe import WindowMoE

    c = config
    seq, batch = traffic["seq"], traffic["global_batch"]
    if seq > c["max_position_embeddings"]:
        raise SystemExit(f"chipbench: seq {seq} exceeds the model's positions")
    if not all(len(c[key]) == c["num_hidden_layers"] for key in LISTS):
        raise SystemExit("chipbench: a per-layer list is not "
                         "num_hidden_layers long")

    def pool(seed):
        """``pool_batches`` global batches of ``seq + 1`` ids a sequence,
        uniform over the vocabulary slice: the model reads all but the
        last, the labels are the same ids one to the right."""
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, c["vocab_size"],
                          (traffic["pool_batches"] * batch,
                           seq + 1)).astype(np.int32)
        return ids[:, :-1], ids[:, 1:]

    opt = dict(config["optimizer"])
    flash = seq >= 512
    return types.SimpleNamespace(
        model=WindowMoE(model_config(config)), loss_fn=next_tokens_loss(),
        pool=pool, optimizer=getattr(optax, opt.pop("name"))(**opt),
        flops_per_step=3 * batch * forward_flops_per_sequence(config, seq),
        flash=flash_work(config, seq, batch) if flash else None,
        flash_window=flash_window_work(config, seq, batch) if flash
        else None)


def visible_pairs(seq, window=None):
    """(query, key) pairs the mask leaves, a sequence and head: the causal
    triangle, or under a window ``min(i + 1, window)`` keys for query i."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _layer_pairs(config, seq, layer):
    sliding = config["layer_types"][layer] == "sliding_attention"
    return visible_pairs(seq, config["sliding_window"] if sliding else None)


def forward_flops_per_sequence(config, seq):
    """Matrix-multiply operations the forward pass requires for one sequence
    (2 per multiply-add); the backward pass requires twice that again."""
    c = config
    D, d, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    expert = 3 * D * c["moe_intermediate_size"]
    held_share = c["num_experts"] / config["published"]["num_experts"]
    total = 2 * seq * D * c["vocab_size"]                   # the head
    for layer, heads in enumerate(c["num_attention_heads_per_layer"]):
        # wq and wo, wk and wv, the gate; QK^T and PV over the visible pairs
        total += 2 * seq * D * (2 * heads * d + 2 * kv * d + heads) \
            + 2 * heads * _layer_pairs(c, seq, layer) * 2 * d
        if c["mlp_layer_types"][layer] == "dense":
            total += 2 * seq * 3 * D * c["intermediate_size"]
        else:
            total += 2 * seq * (
                D * config["published"]["num_experts"]      # router
                + 3 * D * c["shared_expert_intermediate_size"]
                + c["num_experts_per_tok"] * held_share * expert)
    return total


def _flash_work(config, seq, batch, kinds):
    """Operations and bytes one training step requires of the attention
    kernels of the layers whose kind is in ``kinds``: with P visible pairs a
    sequence and head and depth D, forward QK^T and PV are 2 P x 2 D,
    backward the score recomputation, dQ, dK, dP and dV 2 P x 5 D; q, k, v,
    o read or written once forward, and q, k, v, o, dO, dQ, dK, dV once
    backward, in the compute type's 2 bytes."""
    c, d = config, config["head_dim"]
    flops = nbytes = 0
    for layer, heads in enumerate(c["num_attention_heads_per_layer"]):
        if c["layer_types"][layer] in kinds:
            flops += batch * heads * 2 * _layer_pairs(c, seq, layer) * 7 * d
            nbytes += batch * heads * seq * 12 * d * 2
    return {"flops": flops, "bytes": nbytes}


def flash_work(config, seq, batch):
    """Every attention layer's kernels, full and sliding: what
    ``flash_roofline`` divides by all Mosaic time."""
    return _flash_work(config, seq, batch,
                       ("full_attention", "sliding_attention"))


def flash_window_work(config, seq, batch):
    """The sliding layers' kernels alone: visible pairs under the window
    only, so the masked halves of band tiles count as time and not as
    work."""
    return _flash_work(config, seq, batch, ("sliding_attention",))
