"""The ``delta_moe`` family and its plain reference, tiny, on the CPU mesh
through the harness's own ``run_cell``; the configuration file against the
published configuration and its cuts; the family's parameter count at the
published widths and its work functions against hand counts; the three
readers of Kimi delta attention's scopes on a made-up run, and the cells
they apply to. Sizes are overridden here, not by an option of the program;
the real configuration's other keys are used as they are."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import delta_work, harness, kda_scopes, peaks, scopes, trace
from chipbench.families import delta_moe as family
from horovod_tpu.analysis.hlo import HloInstruction
from horovod_tpu.core import telemetry as T

TINY = {"hidden_size": 32, "intermediate_size": 64,
        "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 16,
        "num_attention_heads": 2, "head_dim": 8, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
        "num_experts": 3, "vocab_size": 128, "num_hidden_layers": 4,
        "layer_group_size": 3, "n_group": 4, "topk_group": 2,
        "num_experts_per_tok": 4}
# the published config.json's keys that set a shape or a computation, as
# the catalog of public architectures reads them from the source
PUBLISHED = {
    "first_k_dense_replace": 2, "gated_attention_proj_granularity_type":
    "head_wise", "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "n_group": 8, "no_kda_lora": True, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 512, "num_experts_per_tok": 8,
    "num_hidden_layers": 42, "num_key_value_heads": 32,
    "num_kv_heads_for_linear_attn": 0, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_theta": 6000000, "routed_scaling_factor":
    2.5, "scoring_func": "sigmoid", "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "use_qk_norm": True, "v_head_dim": 128, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}


def _config():
    with open(os.path.join(harness.HERE, "configs",
                           "ling-3.0-flash.json")) as f:
        return json.load(f)


def _tiny_config():
    config = _config()
    config.update(TINY)
    config["published"]["num_experts"] = 16
    config["deployment"].update(first_expert=2, layers=[0, 1, 2, 3])
    config["assumed"]["compute_dtype"] = "float32"
    config["tolerance"]["loss_rel"] = 1e-4      # float32 against float32
    return config


def test_tiny_cell_runs_and_agrees_with_its_reference(capsys):
    line = harness.run_cell(
        {"name": "tiny", "chips": 8}, _tiny_config(),
        {"seq": 80, "global_batch": 8, "pool_batches": 3}, seed=2147483659,
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
    cut = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts": 8, "vocab_size": 19648,
           "num_nextn_predict_layers": 0}
    assert config["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        assert config[key] == cut.get(key, value), key
        if key in cut:
            assert config["published"][key] == value, key
    limits = config["expert_swiglu_limit_list"], \
        config["share_expert_swiglu_limit_list"]
    assert [len(l) for l in limits] == [42, 42]
    layers = config["deployment"]["layers"]
    assert layers == [0, 2, 3, 4, 5, 6, 7]
    assert family.layer_kinds(config) == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert not any(l[i] for l in limits for i in layers)
    assert config["deployment"]["chips_sharing_a_layer"] == 64
    assert 64 * config["num_experts"] == PUBLISHED["num_experts"]
    assert 8 * config["vocab_size"] == PUBLISHED["vocab_size"]
    model = family.model_config(config)
    assert (model.num_experts, model.experts_held, model.first_expert,
            model.first_k_dense_replace, model.layer_indices,
            model.n_group, model.topk_group, model.bias_update_rate) == \
        (512, 8, 0, 1, (0, 2, 3, 4, 5, 6, 7), 8, 4, 0.001)
    attention = model.attention
    assert (attention.q_lora_rank, attention.use_qk_norm,
            attention.head_gate) == (None, True, True)


def test_the_family_refuses_what_the_model_does_not_implement():
    for key, value in (("kda_safe_gate", False), ("use_kda_lora", True),
                       ("group_norm_size", 2), ("num_nextn_predict_layers",
                                                1)):
        config = _config()
        config[key] = value
        with pytest.raises(SystemExit, match=key):
            family.check(config)
    config = _config()
    config["expert_swiglu_limit_list"][5] = 7
    with pytest.raises(SystemExit, match="SwiGLU limits"):
        family.check(config)


def test_the_cell_holds_884_456_768_parameters():
    from horovod_tpu.models.delta_moe import DeltaMoE
    model = DeltaMoE(family.model_config(_config()))
    shapes = jax.eval_shape(
        lambda r, x: model.init(r, x, train=False),
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sizes = {"/".join(str(getattr(k, "key", k)) for k in path):
             int(np.prod(leaf.shape)) for path, leaf in
             jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert sum(sizes.values()) == 884_456_768
    layer = lambda i, part="": sum(v for k, v in sizes.items()
                                   if k.startswith(f"layer_{i}/{part}"))
    assert [layer(i) for i in range(7)] == [110_240_928] + \
        [117_449_888] * 3 + [86_366_080] + [117_449_888] * 2
    assert layer(0, "attn/") == 63_049_888
    assert layer(4, "attn/") == 31_966_080
    top = lambda name: sum(v for k, v in sizes.items()
                           if k.split("/")[0] == name)
    assert top("embedding") == top("lm_head") == 19648 * 2560


def test_required_work_against_a_hand_count():
    """seq 8, batch 3, tiny widths: every term written out."""
    config = _tiny_config()
    seq, batch, D, H, d = 8, 3, 32, 2, 8
    pairs = 8 * 9 // 2
    flash = family.flash_work(config, seq, batch)
    assert flash["flops"] == batch * H * 2 * pairs * (4 * 16 + 3 * 12)
    assert flash["bytes"] == batch * H * seq * (6 * 16 + 6 * 12) * 2
    delta = 2 * seq * D * (6 * H * d + H)
    latent = 2 * seq * (D * H * 16 + D * (16 + 8) + 16 * H * (8 + 12)
                        + H * 12 * D + D * H) + 2 * H * pairs * (16 + 12)
    expert_layer = 2 * seq * (D * 16 + 3 * D * 16 + 4 * (3 / 16) * 3 * D * 16)
    want = (3 * delta + latent + 2 * seq * 3 * D * 64     # dense layer 0
            + 3 * expert_layer + 2 * seq * D * 128)
    assert family.forward_flops_per_sequence(config, seq) == want
    task = family.build(config, {"seq": seq, "global_batch": batch,
                                 "pool_batches": 2})
    kda = delta_work.delta_rule_work(seq, batch, H, d, d, 3)
    assert task.kda_scan == kda
    assert task.flops_per_step == 3 * batch * want + kda["flops"]
    assert task.flash is None                   # below 512 tokens
    tokens, labels = task.pool(2147483659)
    assert tokens.shape == labels.shape == (2 * batch, seq)
    assert tokens.max() < 128
    assert (tokens[:, 1:] == labels[:, :-1]).all()


def test_the_delta_rules_work_against_a_hand_count():
    """One chunk of 64 and one head of d_k = d_v = 128, forward: K K^T over
    2,016 pairs and Q K^T over 2,080 at 256 a pair, the solve over 2,016
    pairs at 512, P U over 2,080 at 256, three products of 2 x 64 x 128 x
    128; a step is three times that. Bytes of 64 positions: q, k, g and v,
    o at 128 and beta, three times, and one state written and read."""
    forward = 256 * (2016 + 2080) + 512 * 2016 + 256 * 2080 \
        + 3 * 2 * 64 * 128 * 128
    work = delta_work.delta_rule_work(64, 1, 1, 128, 128, 1)
    assert work["flops"] == 3 * forward
    assert work["bytes"] == 4 * (3 * 64 * (5 * 128 + 1) + 2 * 128 * 128)
    # a length off the chunk counts whole chunks of work, its own bytes
    off = delta_work.delta_rule_work(65, 2, 3, 128, 128, 5)
    assert off["flops"] == 2 * 3 * 5 * 2 * 3 * forward


def test_the_cells_work_at_the_published_widths():
    """About 1,152 MFLOP a token forward outside the delta rule (the delta
    layers' projections 756.0 of it) and 26.7 in the rule's chunks: 14.49
    TFLOP a step of one sequence of 4,096; the latent layer's kernels 0.62
    of it."""
    config = _config()
    forward = family.forward_flops_per_sequence(config, 4096)
    assert round(forward / 4096 / 1e6, 1) == 1152.2
    kda = family.kda_scan_work(config, 4096, 1)
    assert round(kda["flops"] / 3 / 4096 / 1e6, 1) == 26.7
    assert round((3 * forward + kda["flops"]) / 1e12, 2) == 14.49
    flash = family.flash_work(config, 4096, 1)
    assert round(flash["flops"] / 1e12, 2) == 0.62


# --- the readers of Kimi delta attention's scopes ------------------------------------

FWD = "jit(step)/jvp(hvd_forward)/DeltaMoE/layer_0/attn/hvd_kda/"
BWD = "jit(step)/transpose(jvp(hvd_forward))/DeltaMoE/layer_1/attn/hvd_kda/"
MS = 1_000_000


def _inst(name, op_name, target=""):
    opcode = trace.op_name("%" + name + " = ")
    return HloInstruction(name, opcode, op_name, "main",
                          opcode in ("while", "conditional", "call"),
                          target, ())


def _made_up_run():
    """Two steps of 100 ms: a delta layer's projection forward (2 ms), its
    rule forward (4, a loop body's operation) and backward (5, under the
    loop's umbrella of the same interval), its output product backward
    (3), a Mosaic kernel under the delta scope that the readers leave out
    (6), and an operation of no delta scope (8)."""
    leaves = [("fusion.1", FWD + "qkv_proj/dot_general", 0, 2),
              ("fusion.3", FWD + "hvd_kda_scan/while/body/dot_general", 2, 4),
              ("fusion.2", BWD + "o_proj/dot_general", 6, 3),
              ("while.1", BWD + "hvd_kda_scan/while", 9, 5),
              ("fusion.4", BWD + "hvd_kda_scan/while/body/add_any", 9, 5),
              ("hvd_flash_fwd.1", FWD + "pallas_call", 14, 6),
              ("fusion.5", "jit(step)/jvp(hvd_forward)/DeltaMoE/layer_4/"
                           "attn/hvd_mla/dot_general", 20, 8)]
    instructions = {name: _inst(name, op) for name, op, _, _ in leaves}
    instructions["hvd_flash_fwd.1"] = _inst(
        "hvd_flash_fwd.1", FWD + "pallas_call", scopes.MOSAIC)
    ops = [(f"%{name} = f32[8]{{0}} x(", (100 * k + start) * MS,
            (100 * k + start + ms) * MS)
           for k in range(2) for name, _, start, ms in leaves]
    lines = []
    return types.SimpleNamespace(
        cell={"chips": 1, "config": {"family": "delta_moe"},
              "traffic": {"seq": 4096}},
        trace=trace.Reduced((0, 200 * MS), [100 * MS] * 2, ops, [], 1),
        program=T.Program("train_step", instructions, 0.5, False),
        task=types.SimpleNamespace(kda_scan={"flops": 197e12 * 0.0018,
                                             "bytes": 819e9 * 0.0045}),
        peaks=peaks.PEAKS["TPU v5 lite"],
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


def test_the_delta_readers_on_a_made_up_run(made_up):
    readers = harness.layer_metrics()
    kda, scan, roof = (readers[name] for name in (
        "kda_ms", "kda_scan_ms", "kda_scan_roofline"))
    assert (kda.UNIT, kda.LAYER, scan.UNIT, scan.LAYER, roof.UNIT,
            roof.LAYER) == ("ms", "model", "ms", "kernels", "%", "kernels")
    assert kda.MOVES == scan.MOVES == roof.MOVES == "samples_per_s_chip"
    # the umbrella and the Mosaic kernel are left out
    assert kda.read(made_up) == pytest.approx(2 + 4 + 3 + 5, rel=1e-12)
    assert scan.read(made_up) == pytest.approx(4 + 5, rel=1e-12)
    # memory bound: 4.5 ms of bytes against 9 ms a step in the rule
    assert roof.read(made_up) == pytest.approx(50.0, rel=1e-9)
    joined, said = made_up.lines[0], made_up.lines[-1]
    assert joined["what"] == "program_map" and joined["joined_share"] == 1.0
    assert said["what"] == "kda_scan_roofline" and said["bound"] == "memory"
    assert said["memory_ms"] == pytest.approx(4.5)
    assert set(made_up.asked) == {"train_step"}
    assert set(made_up.kda_scopes) == set(kda_scopes.SCOPES)


def test_the_delta_readers_apply_to_the_new_cell_alone(monkeypatch):
    readers = harness.layer_metrics()
    for name in ("kda_ms", "kda_scan_ms", "kda_scan_roofline"):
        applies = {w["name"] for w in harness._json(os.path.join(
            harness.ROOT, "BENCHMARK.json"))["workloads"]
            if readers[name].applies(dict(
                w, config=harness.resolve(w["name"])[1],
                traffic=harness.resolve(w["name"])[2]))}
        assert applies == {"ling-3.0-flash-s4096"}, name
    cell = _made_up_run().cell
    monkeypatch.delattr(T, "program")       # a library without the map
    for name in ("kda_ms", "kda_scan_ms", "kda_scan_roofline"):
        assert not readers[name].applies(cell)
