"""``chipbench/scopes.py`` (the traced window's leaf operations joined by
instruction name with the step program's own map) on the recorded trace that
``test_chipbench_phases.py`` uses, with a map made of that trace's own
``tf_op`` stats, and on made-up windows; and the 21 readers over it on a
made-up run."""

import os
import types

import pytest
from jax.profiler import ProfileData

from chipbench import harness, phases, scopes, trace
from horovod_tpu.analysis.hlo import HloInstruction
from horovod_tpu.core import telemetry as T

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
READERS = harness.layer_metrics()
FWD = "jit(step)/jvp(hvd_forward)/Model/"
BWD = "jit(step)/transpose(jvp(hvd_forward))/Model/"
REMAT = BWD + "jvp(hvd_forward)/Model/checkpoint/rematted_computation/"


def _inst(name, op_name="", target="", parts=(), computation="main"):
    opcode = trace.op_name("%" + name + " = ")
    return HloInstruction(name, opcode, op_name, computation,
                          opcode in ("while", "conditional", "call", "cond"),
                          target, tuple(parts))


def _event(name, start_ms, ms):
    return (f"%{name} = f32[8]{{0}} x(", int(start_ms * MS),
            int((start_ms + ms) * MS))


def _reduced(ops, steps=2, step_ms=100):
    return trace.Reduced((0, steps * step_ms * MS), [step_ms * MS] * steps,
                         ops, [], 1)


# --- the recorded trace ---------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixture_phases_trace.textproto")) as f:
        text = f.read()
    profile = phases.parse(ProfileData.text_proto_to_serialized_xspace(text))
    # the map, from the trace's own stats: what the program's text says of
    # each instruction is what the profiler copied into the event's tf_op
    instructions = {}
    for ev in trace._line(profile.planes[0], "XLA Ops").events:
        name = trace._OP_LABEL.match(ev.name).group(1)
        instructions[name] = _inst(name, ev.scope.rstrip(":"))
    return profile, trace.reduce(ProfileData.from_text_proto(text)), \
        instructions


def test_the_join_gives_the_phases_the_tf_op_stat_gives(recorded):
    profile, reduced, instructions = recorded
    theirs = phases.by_phase(profile)
    busy = theirs.pop("busy")
    joined = scopes.join(reduced, instructions)
    assert set(joined.phase_ms) == set(theirs)
    for phase, seconds in theirs.items():       # to the nanosecond
        assert round(joined.phase_ms[phase] * 1e6 * reduced.steps) == \
            round(seconds * 1e9 * reduced.steps), phase
    assert sum(joined.phase_ms.values()) == pytest.approx(1e3 * busy,
                                                          rel=1e-12)
    assert joined.facts["joined_share"] == 1.0
    assert joined.facts["instructions"] == len(instructions) == 15
    assert joined.phase_ms["forward"] > 0 and joined.phase_ms["other"] > 0


def test_an_instruction_the_map_lacks_exits_over_a_thousandth_of_busy(
        recorded):
    _, reduced, instructions = recorded
    durations = {}
    for name, start, end in reduced.ops:
        label = trace._OP_LABEL.match(name).group(1)
        durations[label] = durations.get(label, 0) + end - start
    busy = reduced.busy_s * 1e9
    small = min(durations, key=durations.get)
    assert durations[small] < 1e-3 * busy
    fewer = {k: v for k, v in instructions.items() if k != small}
    joined = scopes.join(reduced, fewer)
    assert 0.999 < joined.facts["joined_share"] < 1.0
    fewer.pop("fusion.503")
    with pytest.raises(SystemExit) as e:
        scopes.join(reduced, fewer)
    assert "2 operations the step program's map does not know" in str(
        e.value.code) and "%fusion.503" in str(e.value.code)


def test_a_map_without_hvd_forward_exits(recorded):
    _, reduced, instructions = recorded
    bare = {k: i._replace(op_name=i.op_name.replace("hvd_forward", "f"))
            for k, i in instructions.items()}
    with pytest.raises(SystemExit) as e:
        scopes.join(reduced, bare)
    assert "holds no 'hvd_forward' scope in 15 instructions" in str(
        e.value.code)


# --- made-up windows ------------------------------------------------------------------

def test_an_umbrella_over_two_leaves_is_dropped():
    ops, instructions = [], {}
    for k in range(2):
        t = 100 * k
        ops += [_event("fusion.1", t, 10), _event("while.6", t + 10, 30),
                _event("fusion.2", t + 10, 20), _event("fusion.3", t + 30, 10),
                _event("cond.72", t + 40, 5), _event("fusion.4", t + 40, 5)]
    instructions = {
        "fusion.1": _inst("fusion.1", FWD + "dot"),
        "while.6": _inst("while.6", FWD + "while"),
        "fusion.2": _inst("fusion.2", FWD + "hvd_loop_head/dot", "", (),
                          "body"),
        "fusion.3": _inst("fusion.3", REMAT + "hvd_loop_head/exp", "", (),
                          "body"),
        "cond.72": _inst("cond.72", ""),
        "fusion.4": _inst("fusion.4", BWD + "moe/hvd_moe_route/sort", "", (),
                          "branch")}
    joined = scopes.join(_reduced(ops), instructions)
    assert joined.phase_ms == pytest.approx({
        "forward": 30, "remat": 10, "backward": 5, "optimizer": 0, "other": 0,
        "exchange_pack": 0, "exchange_reduce": 0, "exchange_unpack": 0})
    assert joined.scope_ms["hvd_loop_head"] == pytest.approx(30)
    assert joined.scope_ms["hvd_moe_route"] == pytest.approx(5)
    assert set(joined.scope_ms) == set(scopes.MODEL_SCOPES)


def test_classes_that_do_not_sum_to_the_busy_time_exit():
    # an umbrella whose body left no event: 20 of 30 ms busy are in no class
    ops = [_event("fusion.1", 0, 10), _event("while.6", 10, 20),
           _event("fusion.1", 100, 10), _event("while.6", 110, 20)]
    with pytest.raises(SystemExit) as e:
        scopes.join(_reduced(ops), {
            "fusion.1": _inst("fusion.1", FWD + "dot"),
            "while.6": _inst("while.6", "")})
    assert "sum to 10.0000 ms a step and the device was busy 30.0000" in str(
        e.value.code)


@pytest.mark.parametrize("op_name,scope", [
    (FWD + "layer_1/hvd_mla/dot_general", "hvd_mla"),
    (FWD + "hvd_mtp/block/hvd_mla/q_norm/mul", "hvd_mla"),
    (FWD + "hvd_mtp/block/moe/hvd_moe_route/top_k", "hvd_moe_route"),
    (FWD + "hvd_mtp/join/dot_general", "hvd_mtp"),
    (REMAT + "layer_2/hvd_attn_window/hvd_head_gate/logistic",
     "hvd_attn_window"),
    (BWD + "hvd_loop_pass/layer_0/attn/dot", "hvd_loop_pass"),
    ("jit(step)/hvd_optimizer/add", None), ("", None),
])
def test_an_operation_counts_under_its_innermost_model_scope(op_name, scope):
    assert scopes.innermost(op_name) == scope


def test_a_fusion_across_phases_is_split_by_root_and_by_bytes():
    ops = [_event("fusion.1", 0, 8), _event("fusion.2", 8, 2),
           _event("fusion.1", 100, 8), _event("fusion.2", 108, 2)]
    joined = scopes.join(_reduced(ops), {
        "fusion.1": _inst("fusion.1", BWD + "dot", "", [
            (BWD + "dot", 100), ("jit(step)/hvd_optimizer/add", 300),
            ("", 999)]),
        "fusion.2": _inst("fusion.2", FWD + "dot", "", [(FWD + "dot", 8),
                                                        ("", 8)])})
    facts = joined.facts
    assert facts["fused_across_phases_ms"] == pytest.approx(8)
    assert facts["across_by_root_ms"] == pytest.approx({"backward": 8})
    assert facts["across_by_bytes_ms"] == pytest.approx(
        {"backward": 2, "optimizer": 6})
    assert joined.phase_ms["backward"] == pytest.approx(8)  # by its root


# --- the readers on a made-up run -----------------------------------------------------

def _made_up_run(family="latent_moe", chips=1):
    """Two steps of 300 ms. A step: every phase and every model scope once,
    the ``i``-th for ``i + 1`` ms, a conditional over the expert layer's
    operations, the library's flash kernel and XLA's grouped kernels."""
    leaves = [
        ("fusion.10", FWD + "embed/gather", ""),
        ("hvd_flash_fwd.4", FWD + "hvd_mla/hvd_flash_fwd/pallas_call",
         scopes.MOSAIC),
        ("fusion.11", FWD + "hvd_mla/dot", ""),
        ("fusion.12", FWD + "hvd_mtp/join/dot", ""),
        ("fusion.13", FWD + "moe/hvd_moe_route/sort", ""),
        ("fusion.14", FWD + "moe/cond/branch_1_fun/hvd_moe_dispatch/gather",
         ""),
        ("fusion.15", FWD + "moe/cond/branch_1_fun/hvd_moe_experts/mul", ""),
        ("ragged-dot-none.3", "ragged-dot-none", scopes.MOSAIC),
        ("ragged-dot-metadata", "ragged-dot-metadata", scopes.MOSAIC),
        ("fusion.16", FWD + "moe/hvd_moe_shared/dot", ""),
        ("fusion.17", FWD + "moe/cond/branch_1_fun/hvd_moe_combine/add", ""),
        ("fusion.18", FWD + "hvd_attn_full/hvd_head_gate/logistic", ""),
        ("fusion.19", FWD + "hvd_attn_window/dot", ""),
        ("fusion.20", FWD + "hvd_loop_pass/layer_0/dot", ""),
        ("fusion.21", REMAT + "hvd_loop_head/dot", ""),
        ("fusion.22", BWD + "hvd_exit_gate/mul", ""),
        ("fusion.23", "jit(step)/shmap_body/hvd_exchange_pack/concatenate",
         ""),
        ("all-reduce.1", "jit(step)/shmap_body/hvd_exchange_reduce/psum", ""),
        ("fusion.24", "jit(step)/shmap_body/hvd_exchange_unpack/slice", ""),
        ("fusion.25", "jit(step)/hvd_optimizer/add", ""),
        ("copy-done.7", "", ""),
    ]
    instructions = {name: _inst(name, op_name, target)
                    for name, op_name, target in leaves}
    instructions["cond.72"] = _inst("cond.72", "")
    ops = []
    for k in range(2):
        t = 300.0 * k
        for i, (name, _, _) in enumerate(leaves):
            if name == "fusion.14":     # the conditional spans 14..17
                ops.append(_event("cond.72", t, 6 + 7 + 8 + 9 + 10 + 11))
            ops.append(_event(name, t, i + 1))
            t += i + 1
    lines = []
    return types.SimpleNamespace(
        cell={"chips": chips, "config": {"family": family},
              "traffic": {"seq": 4096}},
        trace=_reduced(ops, step_ms=300), program=T.Program(
            "train_step", instructions, 0.25, False),
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


EXPECTED = {
    # the first fourteen operations, less the two whose op_name is bare
    "forward_ms": sum(range(1, 15)) - 8.0 - 9.0, "remat_ms": 15.0, "backward_ms": 16.0,
    "exchange_pack_ms": 17.0, "exchange_reduce_ms": 18.0,
    "exchange_unpack_ms": 19.0, "optimizer_ms": 20.0,
    "unscoped_ms": 8.0 + 9.0 + 21.0,    # the bare ragged-dot names, the wait
    "grouped_ms": 8.0 + 9.0, "mla_ms": 3.0, "mtp_ms": 4.0,
    "moe_route_ms": 5.0, "moe_dispatch_ms": 6.0, "moe_experts_ms": 7.0,
    "moe_shared_ms": 10.0, "moe_combine_ms": 11.0, "attn_full_ms": 12.0,
    "attn_window_ms": 13.0, "loop_pass_ms": 14.0, "loop_head_ms": 15.0,
    "exit_gate_ms": 16.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_on_a_made_up_run(made_up, name):
    reader = READERS[name]
    assert (reader.UNIT, reader.MOVES) == ("ms", "samples_per_s_chip")
    assert reader.read(made_up) == pytest.approx(EXPECTED[name], rel=1e-12)


def test_the_phase_readers_sum_to_the_busy_time_and_the_map_is_made_once(
        made_up):
    phase_readers = ("forward_ms", "backward_ms", "remat_ms", "optimizer_ms",
                     "unscoped_ms", "exchange_pack_ms", "exchange_reduce_ms",
                     "exchange_unpack_ms")
    total = sum(READERS[name].read(made_up) for name in phase_readers)
    assert total == pytest.approx(
        1e3 * made_up.trace.busy_s / made_up.trace.steps, rel=1e-12)
    assert total == pytest.approx(sum(range(1, 22)))
    assert made_up.asked == ["train_step"]
    line, = made_up.lines
    assert line["what"] == "program_map" and line["compiled"] is False
    assert (line["map_seconds"], line["instructions"]) == (0.25, 22)
    assert line["joined_share"] == 1.0
    assert line["fused_across_phases_ms"] == 0.0


def test_a_scope_no_operation_lies_in_reads_zero_not_nothing(made_up):
    made_up.program.instructions.pop("fusion.21")
    made_up.trace = _reduced([op for op in made_up.trace.ops
                              if not op[0].startswith("%fusion.21 ")],
                             step_ms=300)
    assert READERS["loop_head_ms"].read(made_up) == 0.0
    assert READERS["remat_ms"].read(made_up) == 0.0


def test_the_new_readers_are_the_manifest_s_and_apply_by_the_cell():
    declared = {m["name"]: m for m in harness._json(
        harness.ROOT + "/BENCHMARK.json")["per_layer"]}
    assert set(EXPECTED) <= set(declared) and set(EXPECTED) <= set(READERS)
    assert len(EXPECTED) == 21
    assert all(declared[n]["source"] == "device_trace"
               and declared[n]["better"] == "lower" for n in EXPECTED)

    def cells(name):
        return {(family, chips)
                for family in ("bert", "resnet", "latent_moe", "looped",
                               "window_moe")
                for chips in (1, 4)
                if READERS[name].applies(_made_up_run(family, chips).cell)}
    everywhere = cells("forward_ms")
    assert len(everywhere) == 10
    for name in ("backward_ms", "remat_ms", "optimizer_ms", "unscoped_ms"):
        assert cells(name) == everywhere
    for name in ("exchange_pack_ms", "exchange_reduce_ms",
                 "exchange_unpack_ms"):
        assert cells(name) == {c for c in everywhere if c[1] == 4}
    experts = {c for c in everywhere if c[0] in ("latent_moe", "window_moe")}
    for name in ("moe_route_ms", "moe_dispatch_ms", "moe_experts_ms",
                 "moe_shared_ms", "moe_combine_ms", "grouped_ms"):
        assert cells(name) == experts
    for name, family in (("mla_ms", "latent_moe"), ("mtp_ms", "latent_moe"),
                         ("attn_full_ms", "window_moe"),
                         ("attn_window_ms", "window_moe"),
                         ("loop_pass_ms", "looped"),
                         ("loop_head_ms", "looped"),
                         ("exit_gate_ms", "looped")):
        assert cells(name) == {c for c in everywhere if c[0] == family}


def test_readers_apply_to_no_cell_of_a_library_without_the_map(monkeypatch):
    """The parent of the PR that added them: no ``telemetry.program``. The
    harness then expects none of the 21."""
    monkeypatch.delattr(T, "program")
    assert not scopes.available()
    for family in ("bert", "latent_moe", "looped", "window_moe"):
        cell = _made_up_run(family, 4).cell
        assert not any(READERS[name].applies(cell) for name in EXPECTED)
