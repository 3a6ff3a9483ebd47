"""Each family, tiny, on the CPU mesh through the harness's own ``run_cell``
against its plain reference. Sizes are overridden here, not by an option of
the program; the real configuration's other keys are used as they are."""

import json
import os
import time

import pytest

from chipbench import harness, peaks

TINY = {
    "bert": ("bert-large", {
        "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "intermediate_size": 128, "vocab_size": 256,
        "max_position_embeddings": 64},
        {"seq": 32, "global_batch": 16, "pool_batches": 3}),
    "resnet": ("resnet50", {
        "stage_sizes": [1, 1, 1, 1], "width": 8, "num_classes": 10,
        "image_size": 32},
        {"global_batch": 16, "pool_batches": 2}),
}


@pytest.mark.parametrize("family", sorted(TINY))
def test_tiny_cell_runs_and_agrees_with_its_reference(family, capsys):
    name, sizes, traffic = TINY[family]
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        config = json.load(f)
    config["model"].update(sizes)
    config["assumed"]["compute_dtype"] = "float32"
    config["tolerance"]["loss_rel"] = 1e-4      # float32 against float32
    line = harness.run_cell(
        {"name": "tiny", "chips": 8}, config, traffic, seed=2147483659,
        seconds=0.3, traced=False, peaks=peaks.PEAKS["TPU v5 lite"],
        device={"platform": "cpu", "kind": "cpu", "count": 8},
        t_process=time.perf_counter())
    out = capsys.readouterr().out
    assert line["correct"], out
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == set(harness.END_TO_END)
    assert not harness.validate_line(line, harness.END_TO_END, traced=False)
    infos = [json.loads(l) for l in out.splitlines()]
    window = next(i for i in infos if i["info"] == "window")
    assert window["compiled_in_window"] == 0
    assert window["steps"] == line["attempted"]
