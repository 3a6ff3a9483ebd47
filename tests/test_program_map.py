"""The step program's map of itself: the signature the dispatch boundary
keeps at the first call (``train/step_builder.py instrument_dispatch``), the
handle that turns it into ``{instruction name: opcode, scope path, umbrella,
custom-call target, fused parts}`` without compiling
(``core/telemetry.py program``), and the parser (``analysis/hlo.py
program_map``), on the three step kinds over the 8-device CPU mesh."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.analysis import hlo
from horovod_tpu.core import telemetry as T
from horovod_tpu.train import step_builder
from test_spans import _dp_step, _gspmd_step, _pipeline_step

INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)
EXCHANGE = ("hvd_exchange_pack", "hvd_exchange_reduce", "hvd_exchange_unpack")
KINDS = [
    (lambda: _dp_step(hvd.mesh()), "train_step",
     ("jvp(hvd_forward)", "transpose(jvp(hvd_forward))", "hvd_optimizer")
     + EXCHANGE),
    (_pipeline_step, "pipeline_train_step", ("hvd_optimizer",)),
    (_gspmd_step, "gspmd_train_step",
     ("jvp(hvd_forward)", "transpose(jvp(hvd_forward))", "hvd_optimizer")),
]
IDS = [k[1] for k in KINDS]


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv(T.ENABLE_ENV, raising=False)
    T.reset()
    T.install_compile_listener()
    yield
    T.reset()


def _run(build, calls=3):
    step, state, data = build()
    first = (state, *data)
    for _ in range(calls):
        state, loss = step(state, *data)
    assert np.isfinite(float(loss))
    return step, first


def _compile_spans():
    return [s for s in T.spans() if s[0] in ("compile.lower",
                                             "compile.backend")]


# --- the handle, on the three step kinds ---------------------------------------------

@pytest.mark.parametrize("build,what,scopes", KINDS, ids=IDS)
def test_every_instruction_of_the_optimized_text_is_in_the_map(build, what,
                                                               scopes):
    step, first = _run(build)
    names = set(INSTRUCTION.findall(step.lower(*first).compile().as_text()))
    program = T.program(what)
    assert program.what == what and len(names) > 100
    assert names == set(program.instructions)
    assert all(i.name == name for name, i in program.instructions.items())


@pytest.mark.parametrize("build,what,scopes", KINDS, ids=IDS)
def test_the_step_s_scopes_are_found_in_the_map(build, what, scopes):
    _run(build)
    paths = [i.op_name for i in T.program(what).instructions.values()]
    for scope in scopes:
        assert any(scope in p for p in paths), scope
    # a forward operation is not read as a backward one
    if "jvp(hvd_forward)" in scopes:
        assert any("jvp(hvd_forward)" in p and "transpose(" not in p
                   for p in paths)


@pytest.mark.parametrize("build,what,scopes", KINDS, ids=IDS)
def test_making_the_map_compiles_nothing_and_says_so(build, what, scopes):
    _run(build)
    compiles = T.active().registry.counter_value("hvd_compiles_total")
    spans = _compile_spans()
    assert compiles >= 1 and spans
    program = T.program(what)
    assert T.active().registry.counter_value("hvd_compiles_total") == compiles
    assert _compile_spans() == spans
    assert program.compiled is False and 0 < program.map_seconds < 5
    event, = [e for e in T.active().ring.events() if e["kind"] == "program"]
    every = program.instructions.values()
    assert event["what"] == what and event["compiled"] is False
    assert event["instructions"] == len(program.instructions)
    assert event["fusions"] == sum(i.opcode == "fusion" for i in every) > 0
    assert event["umbrellas"] == sum(i.umbrella for i in every)
    assert 0 < event["scoped_share"] < 1
    assert event["map_seconds"] == pytest.approx(program.map_seconds,
                                                 abs=1e-6)


@pytest.mark.parametrize("build,what,scopes", KINDS, ids=IDS)
def test_a_second_request_returns_the_cached_map(build, what, scopes,
                                                 monkeypatch):
    _run(build)
    program = T.program(what)

    def no_parse(text):
        raise AssertionError("the map was made twice")
    monkeypatch.setattr(hlo, "program_map", no_parse)
    assert T.program(what) is program
    assert len([e for e in T.active().ring.events()
                if e["kind"] == "program"]) == 1


def test_a_fusion_s_parts_name_their_scopes_and_bytes():
    _run(KINDS[0][0])
    every = T.program().instructions
    fusions = [i for i in every.values() if i.opcode == "fusion"]
    assert fusions and all(i.parts for i in fusions)
    assert any("hvd_optimizer" in op_name and nbytes > 0
               for i in fusions for op_name, nbytes in i.parts)
    # the parts are the fused computation's instructions, which the map
    # holds too, parameters left out
    for fusion in fusions:
        fused = {i.op_name for i in every.values()
                 if i.computation != fusion.computation
                 and i.opcode != "parameter"
                 and i.op_name in dict(fusion.parts)}
        assert fused == set(dict(fusion.parts))
    assert not any(i.parts for i in every.values() if i.opcode != "fusion")


def test_the_pipeline_s_loops_are_umbrellas_over_their_bodies():
    _run(_pipeline_step)
    every = T.program("pipeline_train_step").instructions
    loops = [i for i in every.values() if i.opcode == "while"]
    assert loops and all(i.umbrella for i in loops)
    assert {i.opcode for i in every.values() if i.umbrella} <= set(
        hlo.UMBRELLA_OPCODES)
    bodies = {i.computation for i in every.values()} \
        - {i.computation for i in loops}
    assert bodies       # instructions of other computations are in the map


# --- the signature --------------------------------------------------------------------

def test_calls_after_the_first_keep_nothing_and_walk_nothing(monkeypatch):
    kept = []
    monkeypatch.setattr(T, "register_program",
                        lambda *a: kept.append(a))
    run = step_builder.instrument_dispatch(lambda *a, **k: a, what="unit",
                                           lower=lambda *a, **k: None)
    run(jnp.ones(3), scale=2.0)

    def no_walk(*a, **k):
        raise AssertionError("a later call walked its arguments")
    monkeypatch.setattr(step_builder, "_abstract", no_walk)
    monkeypatch.setattr(jax.tree_util, "tree_map", no_walk)
    clock = step_builder.time.perf_counter_ns
    reads = []
    monkeypatch.setattr(step_builder.time, "perf_counter_ns",
                        lambda: reads.append(1) or clock())
    for _ in range(3):
        run(jnp.ones(3), scale=2.0)
    assert len(kept) == 1 and kept[0][0] == "unit"
    assert len(reads) == 6          # the span's two reads a call, as before
    (args, kwargs) = kept[0][2]
    assert args[0].shape == (3,) and kwargs["scale"].weak_type


def test_the_signature_is_shape_dtype_and_the_sharding_jit_keyed_on():
    mesh = hvd.mesh()
    committed = jax.device_put(
        np.ones((8, 2), np.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
            hvd.RANK_AXIS)))
    free = jnp.ones((4,), jnp.bfloat16)
    a, b, c, d = (step_builder._abstract(x)
                  for x in (committed, free, np.ones((2, 3), np.int32), 3))
    assert (a.shape, a.dtype, a.sharding) == ((8, 2), np.float32,
                                              committed.sharding)
    assert (b.shape, b.dtype, b.sharding) == ((4,), jnp.bfloat16, None)
    assert (c.shape, c.dtype, c.sharding) == ((2, 3), np.int32, None)
    assert d.shape == () and d.weak_type and not c.weak_type


def test_with_telemetry_off_nothing_is_kept_and_the_handle_says_so(
        monkeypatch):
    monkeypatch.setenv(T.ENABLE_ENV, "0")
    T.reset()

    def no_walk(x):
        raise AssertionError("a signature was taken with telemetry off")
    monkeypatch.setattr(step_builder, "_abstract", no_walk)
    _run(KINDS[0][0])
    assert T.active().programs == {}
    with pytest.raises(LookupError, match="telemetry is off"):
        T.program()


def test_a_step_kind_that_has_not_run_is_named_with_those_that_have():
    _run(KINDS[0][0], calls=1)
    with pytest.raises(LookupError, match=r"no 'gspmd_train_step' step has "
                       r"made its first call.*\['train_step'\]"):
        T.program("gspmd_train_step")


def test_a_later_step_of_the_same_kind_replaces_the_kept_one():
    _run(KINDS[0][0], calls=1)
    first = T.program()
    step, _ = _run(KINDS[0][0], calls=1)
    second = T.program()
    assert second is not first
    assert set(second.instructions) == set(first.instructions)


def test_a_step_built_without_its_lower_keeps_nothing():
    run = step_builder.instrument_dispatch(lambda *a: a, what="unit_step")
    assert run(1, 2) == (1, 2)
    assert T.active().programs == {}


# --- the parser -----------------------------------------------------------------------

TPU_TEXT = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/x/step.py"

%fused_computation.7 (param_0.1: f32[8,4], param_1.2: f32[8,4]) -> (f32[8,4], bf16[8,4]) {
  %param_0.1 = f32[8,4]{1,0:T(8,128)} parameter(0)
  %param_1.2 = f32[8,4]{1,0:T(8,128)} parameter(1)
  %add.3 = f32[8,4]{1,0:T(8,128)} add(%param_0.1, %param_1.2), metadata={op_name="jit(step)/hvd_optimizer/add" stack_frame_id=4}
  %mul.4 = f32[8,4]{1,0:T(8,128)} multiply(%add.3, %param_1.2), metadata={op_name="jit(step)/hvd_optimizer/add" stack_frame_id=4}
  %convert.5 = bf16[8,4]{1,0:T(8,128)(2,1)} convert(%mul.4), metadata={op_name="jit(step)/transpose(jvp(hvd_forward))/Net/dot_general"}
  ROOT %tuple.6 = (f32[8,4]{1,0:T(8,128)}, bf16[8,4]{1,0:T(8,128)(2,1)}) tuple(%mul.4, %convert.5)
}

%region_2.5 (arg: (f32[8,4])) -> (f32[8,4]) {
  %arg = (f32[8,4]{1,0}) parameter(0)
  %ragged-dot-none.1 = bf16[8,4]{1,0:T(8,128)(2,1)S(1)} custom-call(%arg), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true"}, metadata={op_name="ragged-dot-none"}, backend_config={"custom_call_config":{"body":"bW9kdWxlIHsKICBmdW5j=="}}
  ROOT %copy-done.2 = f32[8,4]{1,0} copy-done(%arg)
}

ENTRY %main.9 (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0:T(8,128)} parameter(0)
  %fusion.4749 = (f32[8,4]{1,0:T(8,128)}, bf16[8,4]{1,0:T(8,128)(2,1)}) fusion(%p, %p), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/transpose(jvp(hvd_forward))/Net/dot_general" stack_frame_id=9}
  %hvd_flash_fwd.4 = (bf16[8,4]{1,0}, f32[8,1]{1,0}) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(hvd_forward)/Net/jit(_fa_call)/hvd_flash_fwd/pallas_call" stack_frame_id=15}
  %custom-call.6 = bf16[8,4]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %while.6 = (s32[]{:T(128)}, f32[8,4]{1,0}) while(%p), condition=%region_2.5, body=%region_2.5, metadata={op_name="jit(step)/jvp(hvd_forward)/while"}
  %cond.72 = (f32[8,4]{1,0}) conditional(%p, %p, %p), branch_computations={%region_2.5, %region_2.5}, metadata={op_name="jit(step)/jvp(hvd_forward)/moe/cond"}
  %all-reduce-start.1 = f32[8,4]{1,0} all-reduce-start(%p), replica_groups={{0,1}}, metadata={op_name="jit(step)/hvd_exchange_reduce/psum"}
  ROOT %all-reduce-done.1 = f32[8,4]{1,0} all-reduce-done(%all-reduce-start.1), metadata={op_name="jit(step)/hvd_exchange_reduce/psum"}
}
'''


def test_the_parser_on_a_module_written_as_the_tpu_compiler_writes_it():
    every = hlo.program_map(TPU_TEXT)
    assert set(every) == set(INSTRUCTION.findall(TPU_TEXT))
    assert len(every) == 17
    fusion = every["fusion.4749"]
    assert (fusion.opcode, fusion.computation, fusion.umbrella) == (
        "fusion", "main.9", False)
    assert "transpose(jvp(hvd_forward))" in fusion.op_name
    # what was fused: two f32[8,4] under the optimizer's scope, one bf16[8,4]
    # under the backward's, the tuple with no scope; parameters left out
    assert dict(fusion.parts) == {
        "jit(step)/hvd_optimizer/add": 2 * 128,
        "jit(step)/transpose(jvp(hvd_forward))/Net/dot_general": 64,
        "": 128 + 64}
    assert every["add.3"].computation == "fused_computation.7"
    # Mosaic calls: the library's by its name, XLA's own by theirs
    assert every["hvd_flash_fwd.4"].target == "tpu_custom_call"
    assert every["ragged-dot-none.1"].target == "tpu_custom_call"
    assert every["custom-call.6"].target == "AllocateBuffer"
    assert every["fusion.4749"].target == ""
    # umbrellas; the halves of an asynchronous operation stay leaf
    assert {n for n, i in every.items() if i.umbrella} == {"while.6",
                                                           "cond.72"}
    assert every["all-reduce-start.1"].opcode == "all-reduce-start"
    assert every["copy-done.2"].opcode == "copy-done"
    # what XLA made without a scope inside a body counts under the body's
    # caller (the profiler's tf_op does the same); the entry's stays bare
    assert every["copy-done.2"].op_name == every["arg"].op_name == \
        "jit(step)/jvp(hvd_forward)/while"
    assert every["ragged-dot-none.1"].op_name == "ragged-dot-none"
    assert every["p"].opcode == "parameter" and every["p"].op_name == ""
    assert every["custom-call.6"].op_name == ""


def test_text_that_is_not_a_module_gives_an_empty_map():
    assert hlo.program_map("") == {}
    assert hlo.program_map("module @jit_step { stablehlo.add }") == {}
