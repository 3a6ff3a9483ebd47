"""The ``conv_moe`` family and its plain reference, tiny, on the CPU mesh
through the harness's own ``run_cell``; the configuration file against the
published configuration and its cuts; the family's parameter count at the
published widths and its work functions against hand counts; the two
readers of the convolution's scopes on a made-up run, and the cells they
apply to. Sizes are overridden here, not by an option of the program; the
real configuration's other keys are used as they are."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import conv_scopes, harness, peaks, scopes, trace
from chipbench.families import conv_moe as family
from horovod_tpu.analysis.hlo import HloInstruction
from horovod_tpu.core import telemetry as T

CONV, FULL = "conv", "full_attention"
TINY = {"num_hidden_layers": 4, "hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "layer_types": [CONV, FULL, CONV, CONV],
        "num_dense_layers": 1, "num_experts": 3, "vocab_size": 128}
# the published config.json's keys, as the catalog of public architectures
# reads them from the source
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
    "layer_types": [CONV, CONV] + [FULL, CONV, CONV, CONV] * 4
    + [FULL, CONV, CONV, FULL, CONV, CONV]}


def _config():
    with open(os.path.join(harness.HERE, "configs",
                           "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def _tiny_config():
    config = _config()
    config.update(TINY)
    config["published"]["num_experts"] = 8
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
    cut = {"num_hidden_layers": 5, "layer_types": [CONV, FULL, CONV, CONV,
                                                   CONV],
           "num_dense_layers": 1, "num_experts": 8, "vocab_size": 16384}
    assert config["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        assert config[key] == cut.get(key, value), key
        if key in cut:
            assert config["published"][key] == value, key
    # one whole period after the leading dense layer, in its ratio
    assert PUBLISHED["layer_types"][2:6] == config["layer_types"][1:]
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert 4 * config["vocab_size"] == PUBLISHED["vocab_size"]
    assert 4 * config["num_experts"] == PUBLISHED["num_experts"]
    model = family.model_config(config)
    assert (model.num_experts, model.experts_held, model.first_expert,
            model.num_dense_layers, model.bias_update_rate) == \
        (32, 8, 0, 1, 0.001)
    assert (model.attention.dim // model.attention.n_heads,
            model.attention.qk_norm) == (64, True)


def test_the_cell_holds_507_820_160_parameters():
    from horovod_tpu.models.conv_moe import ConvMoE
    model = ConvMoE(family.model_config(_config()))
    shapes = jax.eval_shape(
        lambda r, x: model.init(r, x, train=False),
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path):
             int(np.prod(leaf.shape)) for path, leaf in
             jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert sum(sizes.values()) == 507_820_160
    layer = lambda i: sum(v for k, v in sizes.items()
                          if k.startswith(f"layer_{i}/"))
    assert [layer(i) for i in range(5)] == [
        60_827_648, 98_635_904, 104_933_376, 104_933_376, 104_933_376]
    assert sum(v for k, v in sizes.items()
               if k.startswith("layer_1/self_attn/")) == 10_485_888
    assert sum(v for k, v in sizes.items()
               if k.startswith("layer_0/conv/")) == 16_783_360
    top = {k.split("/")[0] for k in sizes}
    assert top == {"embedding", "embedding_norm"} | {
        f"layer_{i}" for i in range(5)}      # no head of its own: tied
    assert sum(v for k, v in sizes.items()
               if k.split("/")[0] == "embedding") == 16384 * 2048


def test_required_work_against_a_hand_count():
    """seq 8, batch 3, tiny widths: every term written out."""
    config = _tiny_config()
    seq, batch, D, H, kv, d = 8, 3, 32, 4, 2, 8
    pairs = 8 * 9 // 2
    flash = family.flash_work(config, seq, batch)
    assert flash["flops"] == batch * H * pairs * (2 * 2 * d + 2 * 5 * d)
    assert flash["bytes"] == batch * H * seq * 2 * (4 * d + 8 * d)
    conv = 2 * seq * (3 * D * D + D * D)
    attention = 2 * seq * D * (2 * H * d + 2 * kv * d) + 2 * H * pairs * 2 * d
    expert_layer = 2 * seq * (D * 8 + 4 * (3 / 8) * 3 * D * 16)
    want = (3 * conv + attention + 2 * seq * 3 * D * 64     # dense layer 0
            + 3 * expert_layer + 2 * seq * D * 128)
    assert family.forward_flops_per_sequence(config, seq) == want
    task = family.build(config, {"seq": seq, "global_batch": batch,
                                 "pool_batches": 2})
    assert task.flops_per_step == 3 * batch * want
    assert task.flash is None                   # below 512 tokens
    tokens, labels = task.pool(2147483659)
    assert tokens.shape == labels.shape == (2 * batch, seq)
    assert tokens.max() < 128
    assert (tokens[:, 1:] == labels[:, :-1]).all()


def test_the_cells_work_at_the_published_widths():
    """About 433 MFLOP a token forward (conv blocks 134.2, attention 54.6,
    experts at expectation 88.1, dense SwiGLU 88.1, head 67.1, routers 0.5),
    21.3 TFLOP a step of two sequences of 8,192; the attention layer's
    kernels 1.92 of it."""
    config = _config()
    forward = family.forward_flops_per_sequence(config, 8192)
    assert round(forward / 8192 / 1e6, 1) == 432.5
    assert round(3 * 2 * forward / 1e12, 2) == 21.26
    flash = family.flash_work(config, 8192, 2)
    assert flash["flops"] == 2 * 32 * 2 * 33_558_528 * 7 * 64
    assert round(flash["flops"] / 1e12, 2) == 1.92


# --- the readers of the convolution's scopes -------------------------------------

FWD = "jit(step)/jvp(hvd_forward)/ConvMoE/layer_0/conv/"
BWD = "jit(step)/transpose(jvp(hvd_forward))/ConvMoE/layer_2/conv/"
MS = 1_000_000


def _inst(name, op_name, target=""):
    opcode = trace.op_name("%" + name + " = ")
    return HloInstruction(name, opcode, op_name, "main",
                          opcode in ("while", "conditional", "call"),
                          target, ())


def _made_up_run():
    """Two steps of 100 ms: the convolution's products forward (2 ms) and
    backward (3), its mixer forward (4) and backward (5, under a
    conditional's umbrella of the same interval), a flash kernel under the
    conv scope that the readers leave out (6), and an operation of no conv
    scope (8)."""
    leaves = [("fusion.1", FWD + "hvd_short_conv/in_proj/dot_general", 0, 2),
              ("fusion.3", FWD + "hvd_short_conv/hvd_conv_mix/mul", 2, 4),
              ("fusion.2", BWD + "hvd_short_conv/out_proj/dot_general", 6, 3),
              ("cond.1", BWD + "hvd_short_conv/cond", 9, 5),
              ("fusion.4", BWD + "hvd_short_conv/hvd_conv_mix/add_any", 9, 5),
              ("hvd_flash_fwd.1", FWD + "hvd_short_conv/pallas_call", 14, 6),
              ("fusion.5", "jit(step)/jvp(hvd_forward)/ConvMoE/layer_1/"
                           "hvd_attn_full/self_attn/dot_general", 20, 8)]
    instructions = {name: _inst(name, op) for name, op, _, _ in leaves}
    instructions["hvd_flash_fwd.1"] = _inst(
        "hvd_flash_fwd.1", FWD + "hvd_short_conv/pallas_call", scopes.MOSAIC)
    ops = [(f"%{name} = f32[8]{{0}} x(", (100 * k + start) * MS,
            (100 * k + start + ms) * MS)
           for k in range(2) for name, _, start, ms in leaves]
    lines = []
    return types.SimpleNamespace(
        cell={"chips": 1, "config": {"family": "conv_moe"},
              "traffic": {"seq": 8192}},
        trace=trace.Reduced((0, 200 * MS), [100 * MS] * 2, ops, [], 1),
        program=T.Program("train_step", instructions, 0.5, False),
        info=lambda **line: lines.append(line), lines=lines)


@pytest.fixture
def made_up(monkeypatch):
    run = _made_up_run()
    asked = []
    monkeypatch.setattr(
        T, "program", lambda what: asked.append(what) or run.program,
        raising=False)
    run.asked = asked
    return run


def test_the_conv_readers_on_a_made_up_run(made_up):
    readers = harness.layer_metrics()
    conv, mix = readers["conv_ms"], readers["conv_mix_ms"]
    assert (conv.UNIT, conv.LAYER, conv.MOVES) == \
        (mix.UNIT, mix.LAYER, mix.MOVES) == ("ms", "model",
                                             "samples_per_s_chip")
    # the umbrella and the Mosaic kernel are left out
    assert conv.read(made_up) == pytest.approx(2 + 4 + 3 + 5, rel=1e-12)
    assert mix.read(made_up) == pytest.approx(4 + 5, rel=1e-12)
    # the join's own checks ran, once, and the sums are kept on the run
    line, = made_up.lines
    assert line["what"] == "program_map" and line["joined_share"] == 1.0
    assert set(made_up.asked) == {"train_step"}
    assert set(made_up.conv_scopes) == set(conv_scopes.SCOPES)


def test_overlapping_conv_operations_count_once(made_up):
    """The mixer's forward moved 1 ms earlier, over the product before it:
    a union of intervals a scope."""
    made_up.trace = trace.Reduced(
        (0, 200 * MS), [100 * MS] * 2,
        [(name, start - MS, end - MS) if name.startswith("%fusion.3 ")
         else (name, start, end) for name, start, end in made_up.trace.ops],
        [], 1)
    readers = harness.layer_metrics()
    assert readers["conv_ms"].read(made_up) == \
        pytest.approx(2 + 4 + 3 + 5 - 1, rel=1e-12)
    assert readers["conv_mix_ms"].read(made_up) == \
        pytest.approx(4 + 5, rel=1e-12)


def test_the_conv_readers_apply_to_the_new_cell_alone(monkeypatch):
    readers = harness.layer_metrics()
    applies = {w["name"] for w in harness._json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["workloads"]
        if readers["conv_ms"].applies(dict(
            w, config=harness.resolve(w["name"])[1],
            traffic=harness.resolve(w["name"])[2]))}
    assert applies == {"lfm2-8b-a1b-s8192"}
    cell = _made_up_run().cell
    assert readers["conv_mix_ms"].applies(cell)
    monkeypatch.delattr(T, "program")       # a library without the map
    assert not readers["conv_ms"].applies(cell)
    assert not readers["conv_mix_ms"].applies(cell)
