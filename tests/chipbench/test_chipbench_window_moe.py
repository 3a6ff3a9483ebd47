"""The ``window_moe`` family and its plain reference, tiny, on the CPU mesh
through the harness's own ``run_cell``; the configuration file against the
catalog's row and its cuts; the family's parameter count at the published
widths and its work functions against hand counts. Sizes are overridden
here, not by an option of the program; the real configuration's other keys
are used as they are."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, peaks
from chipbench.families import window_moe as family

FULL, SLIDING = "full_attention", "sliding_attention"
TINY = {"num_hidden_layers": 4, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_attention_heads_per_layer": [4, 6, 6, 4],
        "layer_types": [FULL, SLIDING, SLIDING, FULL],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
        "sliding_window": 5, "num_experts": 2, "num_experts_per_tok": 3,
        "vocab_size": 128}


def _config():
    with open(os.path.join(harness.HERE, "configs",
                           "laguna-s-2.1.json")) as f:
        return json.load(f)


def _tiny_config():
    config = _config()
    config.update(TINY)
    config["published"].update(          # twice the KV heads, 8 experts
        num_key_value_heads=4, num_experts=8,
        num_attention_heads_per_layer=[8, 12, 12, 8])
    config["deployment"]["first_expert"] = 2
    config["assumed"]["compute_dtype"] = "float32"
    config["tolerance"]["loss_rel"] = 1e-4      # float32 against float32
    return config


def test_tiny_cell_runs_and_agrees_with_its_reference(capsys):
    line = harness.run_cell(
        {"name": "tiny", "chips": 8}, _tiny_config(),
        {"seq": 16, "global_batch": 16, "pool_batches": 3}, seed=2147483659,
        seconds=0.3, traced=False, peaks=peaks.PEAKS["TPU v5 lite"],
        device={"platform": "cpu", "kind": "cpu", "count": 8},
        t_process=time.perf_counter())
    out = capsys.readouterr().out
    assert line["correct"], out
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert not harness.validate_line(line, harness.END_TO_END, traced=False)
    infos = [json.loads(l) for l in out.splitlines()]
    window = next(i for i in infos if i["info"] == "window")
    assert window["compiled_in_window"] == 0
    check = next(i for i in infos if i["info"] == "reference")
    # the second loss is the first update's effect on its own batch
    assert check["system"][1] < check["system"][0]


def test_the_published_configuration_is_the_catalogs_but_for_its_cuts():
    config = _config()
    cut = {"num_hidden_layers": (48, 5), "num_experts": (256, 8),
           "vocab_size": (100352, 12544), "num_attention_heads": (48, 24),
           "num_key_value_heads": (8, 4)}
    lists = ["num_attention_heads_per_layer", "layer_types",
             "mlp_layer_types", "gating_types"]
    assert config["reduced"] == list(cut) + lists
    for key, (published, held) in cut.items():
        assert (config["published"][key], config[key]) == (published, held)
    period = [FULL, SLIDING, SLIDING, SLIDING]
    assert config["published"]["layer_types"] == period * 12
    assert config["layer_types"] == period + [FULL]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["published"]["num_attention_heads_per_layer"] == \
        [48, 72, 72, 72] * 12
    assert config["num_attention_heads_per_layer"] == [24, 36, 36, 36, 24]
    assert config["gating_types"] == ["per_head"] * 5
    # every width as published: hidden, head depth, 6 and 9 query heads a KV
    # head, dense and expert widths, router outputs, choices, the window
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["moe_routed_scaling_factor"]) == \
        (3072, 128, 12288, 1024, 1024, 10, 512, 2.5)
    assert [n // 4 for n in config["num_attention_heads_per_layer"]] == \
        [n // 8 for n in [48, 72, 72, 72, 48]] == [6, 9, 9, 9, 6]
    rope = config["rope_parameters"]
    assert rope["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
    assert rope["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert config["deployment"]["chips_sharing_a_layer"] == 32
    model = family.model_config(config)
    assert (model.num_experts, model.experts_held, model.kv_heads,
            [model.heads(i) for i in range(5)]) == \
        (256, 8, 4, [24, 36, 36, 36, 24])


def test_the_cell_holds_672_125_952_parameters():
    from horovod_tpu.models.window_moe import WindowMoE
    model = WindowMoE(family.model_config(_config()))
    shapes = jax.eval_shape(
        lambda r, x: model.init(r, x, train=False),
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path):
             int(np.prod(leaf.shape)) for path, leaf in
             jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert sum(sizes.values()) == 672_125_952
    layer = lambda i: sum(v for k, v in sizes.items()
                          if k.startswith(f"layer_{i}/"))
    assert [layer(i) for i in range(5)] == [
        135_346_176, 117_295_104, 117_295_104, 117_295_104, 107_821_056]
    assert [v for k, v in sizes.items()
            if k.startswith("layer_1/attn/wg/kernel")] == [3072 * 36]


def test_required_work_against_a_hand_count():
    """seq 8, batch 3, tiny widths: every term written out."""
    config = _tiny_config()
    seq, batch, D, d, kv = 8, 3, 32, 16, 2
    full = 8 * 9 // 2                           # 1 + 2 + ... + 8
    band = (1 + 2 + 3 + 4 + 5) + 3 * 5          # window 5: queries 5..7 see 5
    assert family.visible_pairs(seq) == full
    assert family.visible_pairs(seq, 5) == band
    assert family.visible_pairs(seq, 8) == family.visible_pairs(seq, 9) == full
    flash = family.flash_work(config, seq, batch)
    window = family.flash_window_work(config, seq, batch)
    per_pair = 2 * (2 * d) + 2 * (5 * d)        # forward; backward
    assert window["flops"] == batch * 2 * 6 * band * per_pair
    assert flash["flops"] - window["flops"] == \
        batch * 2 * 4 * full * per_pair
    assert window["bytes"] == batch * 2 * 6 * seq * 2 * (4 * d + 8 * d)
    assert flash["bytes"] - window["bytes"] == \
        batch * 2 * 4 * seq * 2 * (4 * d + 8 * d)

    def attention(heads, pairs):
        return 2 * seq * D * (2 * heads * d + 2 * kv * d + heads) \
            + 2 * heads * pairs * 2 * d
    expert = 3 * D * 16
    expert_layer = 2 * seq * (D * 8 + expert + 3 * (2 / 8) * expert)
    want = (2 * attention(4, full) + 2 * attention(6, band)
            + 2 * seq * 3 * D * 64              # the dense layer
            + 3 * expert_layer + 2 * seq * D * 128)
    assert family.forward_flops_per_sequence(config, seq) == want
    task = family.build(config, {"seq": seq, "global_batch": batch,
                                 "pool_batches": 2})
    assert task.flops_per_step == 3 * batch * want
    assert task.flash is None and task.flash_window is None   # below 512
    tokens, labels = task.pool(2147483659)
    assert tokens.shape == labels.shape == (2 * batch, seq)
    assert tokens.max() < 128
    assert (tokens[:, 1:] == labels[:, :-1]).all()


def test_the_cells_work_at_the_published_widths():
    """ISSUE 35's arithmetic: 20.0 TFLOP a step, 2.89 of them in the two
    full layers' kernels and 0.79 in the three sliding layers'."""
    config = _config()
    step = 3 * family.forward_flops_per_sequence(config, 8192)
    assert round(step / 1e12, 2) == 20.03
    assert family.visible_pairs(8192) == 33_558_528
    assert family.visible_pairs(8192, 512) == 4_063_488
    flash = family.flash_work(config, 8192, 1)
    window = family.flash_window_work(config, 8192, 1)
    assert window["flops"] == 3 * 36 * 2 * 4_063_488 * (4 * 128 + 3 * 128)
    assert flash["flops"] - window["flops"] == \
        2 * 24 * 2 * 33_558_528 * (4 * 128 + 3 * 128)
    assert round(window["flops"] / 1e12, 2) == 0.79
    assert round((flash["flops"] - window["flops"]) / 1e12, 2) == 2.89
