"""The ``latent_moe`` family and its plain reference, tiny, on the CPU mesh
through the harness's own ``run_cell``; and the family's work functions
against a hand count. Sizes are overridden here, not by an option of the
program; the real configuration's other keys are used as they are."""

import json
import os
import time

from chipbench import harness, peaks
from chipbench.families import latent_moe as family

TINY = {"num_hidden_layers": 3, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_attention_heads": 2,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 12, "n_routed_experts": 2,
        "num_experts_per_tok": 3, "vocab_size": 128, "rope_theta": 10000.0}


def _tiny_config():
    with open(os.path.join(harness.HERE, "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    config.update(TINY)
    config["published"]["n_routed_experts"] = 8     # the router's width
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
    with open(os.path.join(harness.HERE, "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert {k: config[k] for k in config["reduced"]} == {
        "num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16160}
    assert {k: config["published"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 40, "n_routed_experts": 256,
        "vocab_size": 129280}
    assert (config["hidden_size"], config["moe_intermediate_size"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["num_experts_per_tok"]) == \
        (2048, 768, 1536, 512, 128, 64, 128, 8)


def test_required_work_against_a_hand_count():
    """seq 4, batch 3, tiny widths: every term written out."""
    config = _tiny_config()
    seq, batch, d, heads = 4, 3, 32, 2
    pairs = 4 + 3 + 2 + 1                       # causal: 1 + 2 + 3 + 4
    qk, dv = 8 + 8, 12
    flash = family.flash_work(config, seq, batch)
    layers = 3 + 1                              # trunk + prediction module
    assert flash["flops"] == layers * batch * heads * (
        2 * pairs * (qk + dv)                   # QK^T, PV
        + 2 * pairs * (3 * qk + 2 * dv))        # scores, dQ, dK; dP, dV
    assert flash["bytes"] == layers * batch * heads * seq * 2 * (
        (2 * qk + 2 * dv)                       # q, k, v, o
        + (4 * qk + 4 * dv))                    # q, k, dQ, dK; v, o, dO, dV
    projections = (d * 24 + 24 * heads * qk + d * (16 + 8)
                   + 16 * heads * (8 + dv) + heads * dv * d)
    attention = 2 * seq * projections + 2 * heads * pairs * (qk + dv)
    expert = 3 * d * 16
    expert_layer = 2 * seq * (d * 8 + expert + 3 * (2 / 8) * expert)
    want = (layers * attention + 2 * seq * 3 * d * 64      # one dense layer
            + 3 * expert_layer                  # two in the trunk + module
            + 2 * seq * (2 * d) * d             # [e ; h] -> d
            + 2 * 2 * seq * d * 128)            # the head, twice
    assert family.forward_flops_per_sequence(config, seq) == want
    task = family.build(config, {"seq": seq, "global_batch": batch,
                                 "pool_batches": 2})
    assert task.flops_per_step == 3 * batch * want
    assert task.flash is None                   # below 512 tokens
    tokens, labels = task.pool(2147483659)
    assert tokens.shape == labels.shape == (2 * batch, seq + 1)
    assert (tokens[:, 1:] == labels[:, :-1]).all() and tokens.max() < 128
