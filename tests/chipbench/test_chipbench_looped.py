"""The ``looped`` family and its plain reference, tiny, on the CPU mesh
through the harness's own ``run_cell``; the configuration's file against the
published sizes; and the family's work functions against a hand count. Sizes
are overridden here, not by an option of the program; the real
configuration's other keys are used as they are."""

import json
import os
import sys
import time
import types

import pytest

from chipbench import harness, peaks
from chipbench.families import looped as family
from chipbench.reference import looped as plain

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "total_ut_steps": 3, "vocab_size": 128, "rope_theta": 10000.0}


def _config():
    with open(os.path.join(harness.HERE, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def _tiny_config():
    config = _config()
    config.update(TINY)
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


@pytest.mark.parametrize("wrong", [
    {"total_ut_steps": 2}, {"exit_entropy_weight": 0.0}],
    ids=["two_passes_for_three", "no_entropy_term"])
def test_a_reference_of_another_loss_misses_the_tiny_cells_tolerance(
        wrong, capsys, monkeypatch):
    """The comparison that decides ``correct`` sees the loop and the entropy
    term: the reference given one pass fewer, or beta = 0, is refused."""
    def program(cfg, devices):
        cfg = {**cfg, "assumed": dict(cfg["assumed"])}
        for key, value in wrong.items():
            (cfg if key in cfg else cfg["assumed"])[key] = value
        return plain.program(cfg, devices)

    monkeypatch.setitem(sys.modules, "chipbench.reference.looped",
                        types.SimpleNamespace(program=program))
    line = harness.run_cell(
        {"name": "tiny", "chips": 8}, _tiny_config(),
        {"seq": 16, "global_batch": 16, "pool_batches": 3},
        seed=2147483659, seconds=0.1, traced=False,
        peaks=peaks.PEAKS["TPU v5 lite"],
        device={"platform": "cpu", "kind": "cpu", "count": 8},
        t_process=time.perf_counter())
    assert not line["correct"], capsys.readouterr().out


def test_the_configuration_is_the_published_one_but_for_its_depth():
    config = _config()
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["published"]["num_hidden_layers"] == 48 \
        == config["max_window_layers"] == len(config["layer_types"])
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["vocab_size"],
            config["total_ut_steps"], config["rope_theta"],
            config["rms_norm_eps"], config["max_position_embeddings"],
            config["tie_word_embeddings"]) == \
        (2048, 5632, 16, 16, 128, 49152, 4, 1000000, 1e-6, 65536, False)
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    held = 2 * 49152 * 2048 + 8 * layer + 2048 + 2049
    assert held == 612_438_017
    assert f"{held:,}" in config["deployment"]["parameters"]
    assert {"sandwich_norms", "loop", "gate", "loss", "exit_entropy_weight",
            "remat_policy", "compute_dtype"} <= set(config["assumed"])


def test_required_work_against_a_hand_count():
    """seq 4, batch 3, tiny widths: every term written out."""
    config = _tiny_config()
    seq, batch, d, heads, depth, passes, layers = 4, 3, 64, 4, 16, 3, 2
    pairs = 4 + 3 + 2 + 1                       # causal: 1 + 2 + 3 + 4
    flash = family.flash_work(config, seq, batch)
    rows = batch * heads * layers * passes
    assert flash["flops"] == rows * (
        2 * pairs * 2 * depth                   # QK^T, PV
        + 2 * pairs * 5 * depth)                # scores, dQ, dK, dP, dV
    assert flash["bytes"] == rows * seq * 2 * (
        4 * depth                               # q, k, v, o
        + 8 * depth)                            # and dO, dQ, dK, dV
    layer = 2 * seq * (4 * d * d + 3 * d * 96) + 2 * heads * pairs * 2 * depth
    want = passes * (layers * layer + 2 * seq * d * 128 + 2 * seq * d)
    assert family.forward_flops_per_sequence(config, seq) == want
    task = family.build(config, {"seq": seq, "global_batch": batch,
                                 "pool_batches": 2})
    assert task.flops_per_step == 3 * batch * want
    assert task.flash is None                   # below 512 tokens
    tokens, labels = task.pool(2147483659)
    assert tokens.shape == labels.shape == (2 * batch, seq)
    assert (tokens[:, 1:] == labels[:, :-1]).all() and tokens.max() < 128


def test_the_cells_required_work_at_the_published_shapes():
    """ISSUE 32's arithmetic: 18.97 TFLOP forward a sequence of 4,096."""
    config, seq = _config(), 4096
    pairs = seq * (seq + 1) // 2
    matrices = 4 * 8 * 2 * seq * (4 * 2048 ** 2 + 3 * 2048 * 5632)
    attention = 4 * 8 * 2 * 16 * pairs * 256
    heads = 4 * 2 * seq * 2048 * 49152
    gates = 4 * 2 * seq * 2048
    forward = family.forward_flops_per_sequence(config, seq)
    assert forward == matrices + attention + heads + gates
    assert [round(x / 1e12, 2) for x in
            (matrices, attention, heads, forward, 3 * forward)] == \
        [13.47, 2.20, 3.30, 18.97, 56.90]
    flash = family.flash_work(config, seq, 1)
    assert flash == {"flops": 512 * 2 * pairs * 7 * 128,
                     "bytes": 512 * seq * 12 * 128 * 2}
    with open(os.path.join(harness.HERE, "traffic",
                           "seq4096-batch1.json")) as f:
        traffic = json.load(f)
    assert (traffic["seq"], traffic["global_batch"]) == (seq, 1)
