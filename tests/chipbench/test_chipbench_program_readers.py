"""The readers of the library's own spans, counters and kernel names
(``chipbench/spans.py``, ``chipbench/kernels.py`` and the seven files under
``layer_metrics/`` that use them) on a made-up run: a span log written by
hand in the harness's order (two blocking calls, ``WARM_STEPS`` warm steps,
the window), the harness's outside timers of the window, and a small
``Reduced``."""

import types

import pytest

from chipbench import harness, spans, trace
from horovod_tpu.core import telemetry as T

MS = 1_000_000
N = 5                                   # steps in the made-up window
FIRST = 2 + harness.WARM_STEPS
READERS = harness.layer_metrics()
NEW = ("input_wait_ms", "input_produce_ms", "dispatch_ms",
       "setup_trace_lower_s", "setup_backend_s", "flash_fwd_ms",
       "flash_bwd_ms")


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv(T.ENABLE_ENV, raising=False)
    T.reset()
    yield
    T.reset()


def _log(name, seq, t0, t1):
    T.record_span(name, seq, t0, t1)


def _made_up_run(wait_ms=0.06, dispatch_ms=3.0, outer_wait_ms=0.08,
                 outer_dispatch_ms=3.8):
    """Steps 100 ms apart. Each step: the worker produces (4 ms) and
    transfers (2 ms) the batch well ahead, the loop waits, then dispatches.
    Set-up before the window: a 9 s trace holding a nested 2 s one, 1 s of
    lowering, 3 s in the backend (all inside the first call), and one more
    backend second during state init; one compile after the window."""
    s = 1000 * MS
    _log("compile.backend", None, 1 * s, 2 * s)             # state init
    first_call = (10 * s, 24 * s)
    _log("compile.trace", None, 12 * s, 14 * s)             # nested, arrives first
    _log("compile.trace", None, 10 * s, 19 * s)
    _log("compile.lower", None, 19 * s, 20 * s)
    _log("compile.backend", None, 20 * s, 23 * s)
    for seq in range(FIRST + N + 3):
        t = 30 * s + seq * 100 * MS
        _log("input.produce", seq, t - 50 * MS, t - 46 * MS)
        _log("input.transfer", seq, t - 46 * MS, t - 44 * MS)
        _log("input.wait", seq, t, t + int(wait_ms * MS))
        if seq == 0:
            _log("step.dispatch", 0, *first_call)
        else:
            _log("step.dispatch", seq, t + MS, t + MS + int(dispatch_ms * MS))
        T.inc("hvd_input_batches_total")
        T.inc("hvd_input_ready_total")
        T.inc("hvd_input_bytes_total", 1000)
    T.inc("hvd_input_batches_total")        # one batch was not ready
    _log("compile.backend", None, 60 * s, 70 * s)           # the reference's
    step = 100 * MS
    ops = []
    for k in range(2):
        t = k * step
        ops += [("%hvd_flash_fwd.4 = (bf16[128,512,64]) custom-call(", t, t + 7 * MS),
                ("%fusion.2 = f32[8] fusion(", t + 7 * MS, t + 20 * MS),
                ("%hvd_flash_bwd_dq.2 = bf16[128,512,64] custom-call(",
                 t + 20 * MS, t + 24 * MS),
                ("%hvd_flash_bwd_dkv.2 = (bf16[128,512,64]) custom-call(",
                 t + 24 * MS, t + 31 * MS)]
    lines = []
    return types.SimpleNamespace(
        cell={"chips": 1, "traffic": {"seq": 512}},
        trace=trace.Reduced((0, 2 * step), [step, step], ops, [], 1),
        waits=[outer_wait_ms / 1e3] * N,
        dispatches=[outer_dispatch_ms / 1e3] * N,
        first_step_s=14.5, state_init_s=7.0,
        info=lambda **line: lines.append(line), lines=lines)


@pytest.mark.parametrize("name,value", [
    ("input_wait_ms", 0.06), ("input_produce_ms", 6.0), ("dispatch_ms", 3.0),
    ("setup_trace_lower_s", 10.0),      # a union: the nested trace counts once
    ("setup_backend_s", 4.0),           # the compile after the window does not
    ("flash_fwd_ms", 7.0), ("flash_bwd_ms", 11.0)])
def test_each_new_reader_on_a_made_up_run(name, value):
    run = _made_up_run()
    reader = READERS[name]
    assert reader.applies(run.cell)
    assert reader.read(run) == pytest.approx(value, rel=1e-9)


def test_the_new_readers_are_the_manifest_s_and_flash_needs_512_tokens():
    declared = {m["name"] for m in harness._json(
        harness.ROOT + "/BENCHMARK.json")["per_layer"]}
    assert set(NEW) <= declared and set(NEW) <= set(READERS)
    short = {"chips": 1, "traffic": {"seq": 128}}
    assert not READERS["flash_fwd_ms"].applies(short)
    assert not READERS["flash_bwd_ms"].applies(short)
    assert READERS["dispatch_ms"].applies(short)


def test_readers_apply_to_no_cell_of_a_library_without_spans(monkeypatch):
    """The parent of the PR that added them: no ``telemetry.spans``, no
    kernel names. The harness then expects none of the seven."""
    import importlib
    flash = importlib.import_module("horovod_tpu.ops.flash_attention")
    monkeypatch.delattr(T, "spans")
    monkeypatch.delattr(flash, "KERNEL_FWD")
    cell = {"chips": 1, "traffic": {"seq": 512}}
    assert not any(READERS[name].applies(cell) for name in NEW)


def test_info_lines_carry_the_parts():
    run = _made_up_run()
    for name in ("input_produce_ms", "dispatch_ms", "setup_trace_lower_s"):
        READERS[name].read(run)
    by = {line["what"]: line for line in run.lines}
    assert by["input_produce"]["produce_ms"] == pytest.approx(4.0)
    assert by["input_produce"]["transfer_ms"] == pytest.approx(2.0)
    assert by["input_produce"]["bytes_a_batch"] == pytest.approx(1000.0)
    batches = FIRST + N + 3
    assert by["input_produce"]["ready_share"] == pytest.approx(
        batches / (batches + 1))
    assert by["dispatch_wrapper"]["wrapper_ms"] == pytest.approx(0.8)
    split = by["first_step_split"]
    assert (split["trace_lower_s"], split["backend_s"]) == (10.0, 3.0)
    assert split["explained_share"] == pytest.approx(13.0 / 14.5)


@pytest.mark.parametrize("name,kwargs,says", [
    ("input_wait_ms", {"wait_ms": 74.0},
     "'input.wait' is not aligned with data_wait_ms"),
    ("dispatch_ms", {"dispatch_ms": 9.0},
     "'step.dispatch' is not aligned with host_dispatch_ms"),
])
def test_a_misaligned_reader_exits_with_both_numbers(name, kwargs, says):
    run = _made_up_run(**kwargs)
    with pytest.raises(SystemExit) as e:
        READERS[name].read(run)
    message = str(e.value.code)
    assert says in message and f"{N} of {N} window steps" in message
    inner = kwargs.get("wait_ms", kwargs.get("dispatch_ms"))
    outer = 0.08 if name == "input_wait_ms" else 3.8
    assert f"span {inner:.4f} ms" in message and f"{outer:.4f} ms" in message


def test_clock_error_is_allowed_in_one_step_in_a_hundred_and_no_more():
    inner = [1.0e-3] * 200
    outer = [1.0e-3 - 0.04e-3] * 200            # within the clock's error
    assert spans.aligned("x", inner, outer, "y") is inner
    outer[:2] = [0.5e-3, 0.5e-3]                # 1% of the steps: allowed
    assert spans.aligned("x", inner, outer, "y") is inner
    outer[2] = 0.5e-3
    with pytest.raises(SystemExit):
        spans.aligned("x", inner, outer, "y")


def test_a_window_step_without_its_span_exits():
    run = _made_up_run()
    run.waits = run.waits + [0.0001] * 10       # a window longer than the log
    with pytest.raises(SystemExit) as e:
        READERS["input_wait_ms"].read(run)
    assert "0 'input.wait' spans with seq" in str(e.value.code)


def test_a_second_prefetcher_s_spans_are_not_guessed_between():
    run = _made_up_run()
    _log("input.wait", FIRST + 1, 5, 6)         # the same seq twice
    with pytest.raises(SystemExit) as e:
        READERS["input_wait_ms"].read(run)
    assert f"2 'input.wait' spans with seq {FIRST + 1}" in str(e.value.code)


def test_a_full_span_log_is_not_read():
    run = _made_up_run()
    for i in range(T.SPAN_LOG):
        _log("unit.fill", i, 0, 1)
    with pytest.raises(SystemExit) as e:
        READERS["setup_backend_s"].read(run)
    assert "span log is full" in str(e.value.code)


def test_input_spans_out_of_order_exit():
    run = _made_up_run()
    log = T.active().span_log
    for i, s in enumerate(log):
        if s[0] == "input.transfer" and s[1] == FIRST:
            log[i] = (s[0], s[1], s[2] + 500 * MS, s[3] + 500 * MS, s[4])
    with pytest.raises(SystemExit) as e:
        READERS["input_produce_ms"].read(run)
    assert "out of order" in str(e.value.code)


def test_a_trace_without_the_named_kernels_reads_nothing():
    run = _made_up_run()
    run.trace = trace.Reduced((0, 200), [100, 100], [
        ("%_fa_call.48 = (bf16[8]) custom-call(", 0, 50)], [], 1)
    assert READERS["flash_fwd_ms"].read(run) is None
    assert READERS["flash_bwd_ms"].read(run) is None
