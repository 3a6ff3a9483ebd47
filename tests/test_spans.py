"""The step's life as spans (core/telemetry.py ``span``/``spans``), the
spans and counters at each layer boundary (Prefetcher, the shared dispatch
wrapper, the compile listener), and the names on the device program (the
``hvd_*`` scopes and the Pallas kernels' instruction names)."""

import contextlib
import threading
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.collectives import Compression
from horovod_tpu.core import telemetry as T
from horovod_tpu.data import Prefetcher
from horovod_tpu.optimizer import distributed
from horovod_tpu.parallel import create_mesh
from horovod_tpu.train import (create_gspmd_train_state,
                               create_pipeline_train_state,
                               create_train_state, make_gspmd_train_step,
                               make_pipeline_train_step, make_train_step,
                               next_token_loss)
from test_ops import BWD_KERNELS, bwd_form, pallas_names  # noqa: F401


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv(T.ENABLE_ENV, raising=False)
    T.reset()
    yield
    T.reset()


# --- the helper ---------------------------------------------------------------

def test_span_records_name_seq_clock_and_thread():
    t0 = time.perf_counter_ns()
    with T.span("unit.work", seq=7):
        time.sleep(0.002)
    t1 = time.perf_counter_ns()
    (name, seq, a, b, tid), = T.spans()
    assert (name, seq, tid) == ("unit.work", 7, threading.get_ident())
    assert t0 <= a < b <= t1 and b - a >= 2_000_000
    c = T.active().registry.export()["c"]
    assert c['hvd_span_seconds_count{name="unit.work"}'] == 1.0
    assert c['hvd_span_seconds_sum{name="unit.work"}'] == pytest.approx(
        (b - a) / 1e9)


def test_nested_spans_give_self_time_by_containment():
    with T.span("outer", 0):
        time.sleep(0.001)
        with T.span("inner", 0):
            time.sleep(0.002)
    (_, _, i0, i1, itid), (_, _, o0, o1, otid) = T.spans()  # inner exits first
    assert otid == itid and o0 <= i0 and i1 <= o1
    self_ns = (o1 - o0) - (i1 - i0)
    assert 1_000_000 <= self_ns < (o1 - o0)
    assert [s[0] for s in T.spans("inner")] == ["inner"]


def test_record_span_logs_clock_reads_taken_elsewhere():
    """What ``span()`` does on exit, for a caller that times now and
    accounts later (the Prefetcher's consumer) or is told a duration (the
    compile listener): the thread is the timer's, whoever records."""
    timer = threading.get_ident()
    th = threading.Thread(
        target=lambda: T.record_span("unit.later", 4, 1_000, 3_001_000, timer))
    th.start()
    th.join(10)
    T.record_span("unit.later", 5, 10, 20)
    assert T.spans() == [("unit.later", 4, 1_000, 3_001_000, timer),
                         ("unit.later", 5, 10, 20, timer)]
    c = T.active().registry.export()["c"]
    assert c['hvd_span_seconds_count{name="unit.later"}'] == 2.0
    assert c['hvd_span_seconds_sum{name="unit.later"}'] == pytest.approx(
        0.003 + 1e-8)


def test_span_is_logged_when_its_body_raises():
    with pytest.raises(KeyError):
        with T.span("unit.raises"):
            raise KeyError("x")
    assert [s[0] for s in T.spans()] == ["unit.raises"]


def test_disabled_switch_is_a_shared_noop_that_reads_no_clock(monkeypatch):
    monkeypatch.setenv(T.ENABLE_ENV, "0")
    T.reset()

    def no_clock():
        raise AssertionError("a disabled span read the clock")
    monkeypatch.setattr(T.time, "perf_counter_ns", no_clock)
    a, b = T.span("unit.off", 1), T.span("unit.other")
    assert a is b
    with a:
        pass
    T.record_span("unit.off", 2, 0, 1)
    assert T.spans() == []
    assert T.active().registry.export()["c"] == {}


def test_span_log_is_bounded():
    assert T.active().span_log.maxlen == T.SPAN_LOG == 16384
    for i in range(T.SPAN_LOG + 5):
        T.record_span("unit.fill", i, 0, 1)
    log = T.spans()
    assert len(log) == T.SPAN_LOG and log[0][1] == 5 and log[-1][1] == \
        T.SPAN_LOG + 4


def test_span_sits_in_a_running_profile_as_hvd_annotation(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with T.span("unit.traced", 3):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.rglob("*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in profile.planes for line in plane.lines
             for ev in line.events}
    assert "hvd:unit.traced" in names


# --- the input layer -----------------------------------------------------------

def _host_batches(n, delay=0.0):
    for i in range(n):
        if delay:
            time.sleep(delay)
        yield (np.full((8, 4), i, np.float32), np.arange(8, dtype=np.int32))


def test_prefetcher_spans_match_by_seq_across_threads():
    with Prefetcher(_host_batches(5)) as feed:
        got = [int(np.asarray(x)[0, 0]) for x, _ in feed]
    assert got == [0, 1, 2, 3, 4]
    main = threading.get_ident()
    produce, transfer, wait = (T.spans("input." + k)
                               for k in ("produce", "transfer", "wait"))
    # the worker also asks the exhausted source once more; the consumer's
    # wait for the end marker is no batch's
    assert [s[1] for s in produce] == [0, 1, 2, 3, 4, 5]
    assert [s[1] for s in transfer] == [0, 1, 2, 3, 4]
    assert [s[1] for s in wait] == [0, 1, 2, 3, 4]
    worker = {s[4] for s in produce + transfer}
    assert len(worker) == 1 and main not in worker
    assert {s[4] for s in wait} == {main}
    for p, x, w in zip(produce, transfer, wait):   # same batch, in order
        assert p[3] <= x[2] and x[3] <= w[3]
    reg = T.active().registry
    assert reg.counter_value("hvd_input_batches_total") == 5.0
    assert reg.counter_value("hvd_input_bytes_total") == 5 * (8 * 4 * 4 + 8 * 4)


def _until(predicate, timeout=10.0):
    end = time.monotonic() + timeout
    while not predicate() and time.monotonic() < end:
        time.sleep(0.005)
    assert predicate()


def test_prefetcher_counts_ready_batches_and_sets_the_gauge_to_the_wait():
    """The consumer only times its wait; the worker accounts it on its next
    round (``close()`` what is left), with the consumer's thread."""
    reg = T.active().registry
    with Prefetcher(_host_batches(3, delay=0.05), depth=1) as feed:
        next(feed)                       # a slow source: nothing is ready
        _until(lambda: T.spans("input.wait"))
        (wait,) = T.spans("input.wait")
        assert wait[1] == 0 and wait[4] == threading.get_ident()
        _until(lambda: reg.counter_value("hvd_input_batches_total") == 1.0)
        assert reg.gauge_value("hvd_step_data_wait_seconds") == \
            (wait[3] - wait[2]) / 1e9 >= 0.03
        assert reg.counter_value("hvd_input_ready_total") == 0.0
        time.sleep(0.15)                 # now the next one is queued
        next(feed)
    assert [s[1] for s in T.spans("input.wait")] == [0, 1]     # close()
    assert reg.counter_value("hvd_input_ready_total") == 1.0
    assert reg.counter_value("hvd_input_batches_total") == 2.0
    assert reg.gauge_value("hvd_step_data_wait_seconds") < 0.03


def test_every_wait_is_accounted_once_whoever_drains():
    """The consumer appends, the worker and ``close()`` both drain: under a
    short switch interval no hand-out is lost or accounted twice."""
    import sys
    n, interval = 400, sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            with Prefetcher(_host_batches(n), depth=3,
                            transfer=lambda b: b) as feed:
                for _ in range(n // 2):
                    next(feed)
            feed._thread.join(10)
            assert not feed._thread.is_alive()
    finally:
        sys.setswitchinterval(interval)
    seqs = sorted(s[1] for s in T.spans("input.wait"))
    assert seqs == sorted(list(range(n // 2)) * 5)
    assert T.active().registry.counter_value("hvd_input_batches_total") == \
        5 * (n // 2)


def test_prefetcher_with_telemetry_off_reads_no_clock(monkeypatch):
    monkeypatch.setenv(T.ENABLE_ENV, "0")
    T.reset()
    from horovod_tpu import data

    def no_clock():
        raise AssertionError("a disabled Prefetcher read the clock")
    monkeypatch.setattr(data.time, "perf_counter_ns", no_clock)
    with Prefetcher(_host_batches(3), transfer=lambda b: b) as feed:
        assert len(list(feed)) == 3
    assert T.spans() == [] and T.active().registry.export()["c"] == {}


# --- the dispatch boundary ------------------------------------------------------

class _TinyLM(nn.Module):
    @nn.compact
    def __call__(self, tokens):
        return nn.Dense(13)(nn.relu(nn.Dense(8)(nn.Embed(13, 8)(tokens))))


class _Net(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        return nn.Dense(4)(nn.relu(nn.remat(nn.Dense)(16)(x)))


def _xent(out, y):
    return optax.softmax_cross_entropy_with_integer_labels(out, y).mean()


def _dp_step(mesh=None):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 4, (16,)))
    opt = distributed(optax.adam(1e-2), compression=Compression.bf16)
    state = create_train_state(_Net(), jax.random.PRNGKey(0), x[:1], opt,
                               mesh=mesh)
    step = make_train_step(_Net(), opt, _xent, mesh=mesh, donate=False,
                           axis_name=hvd.RANK_AXIS if mesh else None)
    return step, state, (x, y)


def _pipeline_step():
    rng = np.random.RandomState(7)
    Ws = jnp.asarray(rng.randn(8, 3, 3).astype(np.float32) * 0.4)
    xs = jnp.asarray(rng.randn(16, 4, 3).astype(np.float32))
    ts = jnp.asarray(rng.randn(16, 4, 3).astype(np.float32))
    opt = optax.sgd(0.1)
    state = create_pipeline_train_state(Ws, opt)
    step = make_pipeline_train_step(
        lambda W, x: jnp.tanh(x @ W), lambda y, t: jnp.mean((y - t) ** 2),
        opt, mesh=create_mesh({"pp": 8}), schedule="gpipe", donate=False)
    return step, state, (xs, ts)


def _gspmd_step():
    mesh = create_mesh({"dp": 8})
    tokens = jnp.asarray(np.random.RandomState(0).randint(1, 13, (8, 6)))
    opt = optax.sgd(0.1)
    state = create_gspmd_train_state(_TinyLM(), opt, jax.random.PRNGKey(0),
                                     tokens, mesh, ())
    step = make_gspmd_train_step(_TinyLM(), opt, mesh, (),
                                 loss_fn=next_token_loss, data_axes=("dp",),
                                 donate=False)
    return step, state, (tokens,)


@pytest.mark.parametrize("build,what", [
    (_dp_step, "train_step"), (_pipeline_step, "pipeline_train_step"),
    (_gspmd_step, "gspmd_train_step")])
def test_step_dispatch_span_once_a_call_with_rising_seq(build, what):
    step, state, data = build()
    other, other_state, _ = build()     # its own ordinals
    for _ in range(3):
        state, loss = step(state, *data)
    other(other_state, *data)
    assert np.isfinite(float(loss))
    assert [s[1] for s in T.spans("step.dispatch")] == [0, 1, 2, 0]
    assert T.active().registry.counter_value(
        "hvd_dispatches_total", what=what) == 4.0


def test_dispatch_with_telemetry_off_reads_no_clock(monkeypatch):
    from horovod_tpu.train import step_builder
    monkeypatch.setenv(T.ENABLE_ENV, "0")
    T.reset()

    def no_clock():
        raise AssertionError("a disabled dispatch boundary read the clock")
    monkeypatch.setattr(step_builder.time, "perf_counter_ns", no_clock)
    run = step_builder.instrument_dispatch(lambda *a: a, what="unit_step")
    assert run(1, 2) == (1, 2)
    assert T.spans() == [] and T.active().registry.export()["c"] == {}


def test_a_kept_series_id_counts_into_the_same_series():
    sid = T.series_id("unit_total", what="a", rank=3)
    T.inc("unit_total", what="a", rank=3)
    T.inc_series("unit_total", sid, 2.0)
    reg = T.active().registry
    assert reg.counter_value("unit_total", what="a", rank=3) == 3.0
    assert reg.export()["c"] == {sid: 3.0}


def test_dispatch_writes_the_timeline_activity_for_every_step_kind(tmp_path):
    from horovod_tpu.tools.timeline import Timeline
    path = tmp_path / "timeline.json"
    tl = hvd.core.context_api.context().timeline = Timeline(str(path))
    step, state, data = _pipeline_step()
    step(state, *data)
    tl.close()
    assert "PIPELINE_TRAIN_STEP" in path.read_text()
    assert "DISPATCH" in path.read_text()


# --- the compile listener --------------------------------------------------------

def test_compile_listener_records_a_first_call_and_nothing_on_the_second():
    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + jnp.float32(time.time_ns() % 7)

    reg = T.active().registry
    x = jnp.ones((3, 5))
    T.set_gauge("hvd_last_step", 41)
    fresh(x).block_until_ready()
    first = {k: len(T.spans("compile." + k))
             for k in ("trace", "lower", "backend")}
    assert all(n >= 1 for n in first.values()), first
    assert reg.counter_value("hvd_compiles_total") == first["backend"]
    t1 = time.perf_counter_ns()
    for name, seq, a, b, _ in T.spans():
        assert name.startswith("compile.") and seq is None and a < b <= t1
    event = [e for e in T.active().ring.events() if e["kind"] == "compile"][-1]
    assert event["hvd_last_step"] == 41.0 and event["seconds"] > 0
    fresh(x).block_until_ready()
    assert {k: len(T.spans("compile." + k)) for k in first} == first
    assert reg.counter_value("hvd_compiles_total") == first["backend"]


def test_short_nested_traces_go_to_the_histogram_and_not_to_the_log():
    trace = "/jax/core/compile/jaxpr_trace_duration"
    for _ in range(3):
        T._on_jax_duration(trace, T.MIN_TRACE_SPAN_S / 2)
    T._on_jax_duration(trace, 0.25)
    T._on_jax_duration("/jax/core/compile/backend_compile_duration", 1e-4)
    T._on_jax_duration("/jax/some/other/event", 5.0)
    log = T.spans()
    assert [s[0] for s in log] == ["compile.trace", "compile.backend"]
    assert log[0][3] - log[0][2] == 250_000_000
    c = T.active().registry.export()["c"]
    assert c['hvd_span_seconds_count{name="compile.trace"}'] == 4.0
    assert c["hvd_compiles_total"] == 1.0


def test_compile_listener_is_registered_once_a_process():
    from jax._src import monitoring
    hvd.shutdown()
    hvd.init()
    hvd.init()
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(T._on_jax_duration) == 1


# --- names on the device program ---------------------------------------------------

def test_lowered_dp_step_carries_forward_backward_and_optimizer_scopes():
    step, state, data = _dp_step()
    hlo = step.lower(state, *data).compile().as_text()
    for scope in ("/jvp(hvd_forward)/", "/transpose(jvp(hvd_forward))/",
                  "/hvd_optimizer/"):
        assert scope in hlo, scope
    # the 8-device mesh exchanges gradients: the three exchange scopes
    for scope in ("hvd_exchange_pack", "hvd_exchange_reduce",
                  "hvd_exchange_unpack"):
        assert f"/{scope}/" in hlo, scope


def test_one_device_step_has_the_phase_scopes_and_no_exchange():
    from jax.sharding import Mesh
    step, state, data = _dp_step(
        Mesh(np.asarray(jax.devices()[:1]), (hvd.RANK_AXIS,)))
    hlo = step.lower(state, *data).compile().as_text()
    for scope in ("/jvp(hvd_forward)/", "/transpose(jvp(hvd_forward))/",
                  "/hvd_optimizer/"):
        assert scope in hlo, scope
    assert "hvd_exchange_reduce" not in hlo and "all-reduce" not in hlo


def test_named_scopes_are_metadata_only(monkeypatch):
    def run():
        step, state, data = _dp_step()
        losses = []
        for _ in range(3):
            state, loss = step(state, *data)
            losses.append(np.asarray(loss))
        return losses

    named = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    step, state, data = _dp_step()
    assert "hvd_" not in step.lower(state, *data).as_text(debug_info=True)
    plain = run()
    assert [a.tobytes() for a in named] == [b.tobytes() for b in plain]


@pytest.mark.parametrize("blocks,bwd_form", [
    ({}, "fused"),
    ({"block_q": 64, "block_k": 64}, "fused_tiled"),
    ({"block_q": 64, "block_k": 64}, "two_pass"),
], ids=["single_tile", "multi_tile", "multi_tile_two_pass"],
    indirect=["bwd_form"])
def test_flash_kernels_carry_their_names(blocks, bwd_form):
    from horovod_tpu.ops.flash_attention import (KERNEL_BWD, KERNEL_FWD,
                                                 flash_attention)
    # three heads: a shape no other test traces, so the jitted backward is
    # traced here (its counter fires per trace, and jit caches traces)
    q = jnp.ones((1, 128, 3, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=False, **blocks).sum()

    assert pallas_names(loss, q, q, q) == ["hvd_flash_fwd"]
    names = sorted(pallas_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q))
    assert names == BWD_KERNELS[bwd_form] + ["hvd_flash_fwd"]
    assert set(names) <= {KERNEL_FWD, *KERNEL_BWD}
    assert len(KERNEL_BWD) == 4 and set(KERNEL_BWD) == {
        n for form in BWD_KERNELS.values() for n in form}
    counted = {k: T.active().registry.counter_value(
        "hvd_flash_bwd_kernels_total", kind=k) for k in BWD_KERNELS}
    assert counted == {**dict.fromkeys(BWD_KERNELS, 0.0), bwd_form: 1.0}


@pytest.mark.parametrize("tokens,groups,denominator", [
    (512, "2", "lanes"), (1024, "1", "lanes"), (48, "1", "rows")],
    ids=["one_tile_of_512", "two_tiles_of_512", "one_tile_of_48"])
def test_flash_forward_counts_its_row_groups(tokens, groups, denominator):
    """``hvd_flash_fwd_kernels_total`` says, once a traced forward, in how
    many row groups a visit is computed (two where a 512-row tile meets the
    sequence's one key tile) and how the denominator is carried (per-lane
    partial sums where a key tile is whole 128-lane tiles)."""
    from horovod_tpu.ops.flash_attention import flash_attention
    # five heads: a shape no other test traces (jit caches traces)
    q = jnp.ones((1, tokens, 5, 64), jnp.bfloat16)
    assert pallas_names(
        lambda q: flash_attention(q, q, q, causal=True), q) == \
        ["hvd_flash_fwd"]
    counted = {(g, d): T.active().registry.counter_value(
        "hvd_flash_fwd_kernels_total", groups=g, denominator=d)
        for g in ("1", "2", "4") for d in ("lanes", "rows")}
    assert {key for key, n in counted.items() if n} == \
        {(groups, denominator)}


@pytest.mark.parametrize("blocks,bwd_form,kind", [
    ({}, "fused", "bwd_fused"),
    ({"block_q": 16, "block_k": 16}, "fused_tiled", "bwd_tiled"),
    ({"block_q": 16, "block_k": 16}, "two_pass", "bwd_two_pass"),
], ids=["single_tile", "multi_tile", "multi_tile_two_pass"],
    indirect=["bwd_form"])
def test_windowed_flash_kernels_carry_their_names_and_count_themselves(
        blocks, bwd_form, kind):
    """A windowed call's kernels have names of their own, so the readers of
    the unwindowed kernels' time see none of it, and
    ``hvd_flash_window_kernels_total`` counts one a traced windowed call,
    where the kernels are chosen, with the band's tiles a (batch*head)."""
    import importlib
    # ``horovod_tpu.ops`` re-exports the function under the module's name
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    # seven heads: a shape no other test traces (jit caches traces)
    q = jnp.ones((1, 64, 7, 16), jnp.float32)

    def loss(q, k, v, window=24):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  **blocks).sum()

    names = sorted(pallas_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q))
    assert names == [n + "_window" for n in BWD_KERNELS[bwd_form]] \
        + ["hvd_flash_fwd_window"]
    assert set(names) <= {fa.KERNEL_FWD_WINDOW, *fa.KERNEL_BWD_WINDOW}
    assert not set(names) & {fa.KERNEL_FWD, *fa.KERNEL_BWD}
    tiles = 1 if bwd_form == "fused" else 4 + 3 + 2   # diagonal, two below
    counted = {k: T.active().registry.counter_value(
        "hvd_flash_window_kernels_total", window=24, band_tiles=tiles,
        kind=k) for k in ("fwd", "bwd_fused", "bwd_tiled", "bwd_two_pass")}
    assert counted == {"fwd": 1.0, "bwd_fused": 0.0, "bwd_tiled": 0.0,
                       "bwd_two_pass": 0.0, kind: 1.0}
    # the unwindowed counter still says which form ran; an unwindowed call
    # counts no windowed kernel
    assert T.active().registry.counter_value(
        "hvd_flash_bwd_kernels_total", kind=bwd_form) == 1.0
    pallas_names(jax.grad(lambda *a: loss(*a, window=None),
                          argnums=(0, 1, 2)), q, q, q)
    assert sum(n for s, n in T.active().registry.export()["c"].items()
               if s.startswith("hvd_flash_window_kernels_total")) == 2.0


def test_pattern_layers_carry_their_scopes_and_count_themselves_by_kind():
    """``models/window_moe.py`` through the data-parallel step: scopes
    ``hvd_attn_full`` / ``hvd_attn_window`` with ``hvd_head_gate`` inside,
    the ``hvd_moe_*`` scopes as they are, and one
    ``hvd_attn_layers_total{kind=,heads=,kv_heads=}`` a traced layer."""
    from horovod_tpu.models.latent_moe import next_tokens_loss
    from horovod_tpu.models.window_moe import (FULL, SLIDING, WindowMoE,
                                               window_moe_tiny)
    hvd.init()
    c = window_moe_tiny(axis_name=hvd.RANK_AXIS)
    tokens = jnp.asarray(np.random.RandomState(8).randint(0, 128, (8, 16)),
                         jnp.int32)
    dopt = distributed(optax.sgd(0.1))
    model = WindowMoE(c)
    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1], dopt)
    labelled = [dict(kind=FULL, heads=8, kv_heads=4),
                dict(kind=SLIDING, heads=12, kv_heads=4)]
    count = lambda: [T.active().registry.counter_value(
        "hvd_attn_layers_total", **l) for l in labelled]
    before = count()
    step = make_train_step(model, dopt, next_tokens_loss())
    text = step.lower(state, tokens, tokens).as_text(debug_info=True)
    for scope in ("hvd_attn_full", "hvd_attn_window", "hvd_head_gate",
                  "hvd_moe_route", "hvd_moe_experts", "hvd_moe_shared"):
        assert f"/{scope}" in text or f'"{scope}/' in text, scope
    assert "hvd_forward)/WindowMoE/layer_1/attn/hvd_attn_window/" \
        "hvd_head_gate/" in text
    assert "/layer_0/attn/hvd_attn_full/" in text
    # trace-time: one a traced layer (two full, two sliding)
    assert [a - b for a, b in zip(count(), before)] == [2.0, 2.0]
    from horovod_tpu.analysis import analyze_step
    assert analyze_step(step, state, tokens, tokens) == []


def test_a_step_counts_one_windowed_forward_and_backward_a_sliding_layer():
    """Two sliding layers of ONE shape: the kernels' own jit keeps one trace
    for both, and ``hvd_flash_window_kernels_total`` still counts two
    forward and two backward calls. (Trace-time: under ``nn.remat`` a layer
    is traced for the forward and again for the backward's recomputation,
    so the forward's count doubles whether or not the policy then keeps the
    kernel's outputs; the kernels of the compiled program are read by their
    names.)"""
    from horovod_tpu.models.latent_moe import next_tokens_loss
    from horovod_tpu.models.window_moe import WindowMoE, window_moe_tiny
    hvd.init()
    model = WindowMoE(window_moe_tiny(use_flash=True))
    tokens = jnp.asarray(np.random.RandomState(8).randint(0, 128, (8, 24)),
                         jnp.int32)
    dopt = distributed(optax.sgd(0.1))
    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1], dopt)
    count = lambda kind: T.active().registry.counter_value(
        "hvd_flash_window_kernels_total", window=5, band_tiles=1, kind=kind)
    before = [count("fwd"), count("bwd_fused")]
    make_train_step(model, dopt, next_tokens_loss()).lower(
        state, tokens, tokens)
    assert [count("fwd") - before[0], count("bwd_fused") - before[1]] == \
        [2.0, 2.0]


def test_adasum_kernels_carry_their_names():
    from horovod_tpu.ops import fused
    a = jnp.ones((4096,), jnp.float32)
    assert pallas_names(fused.fused_norms_dot, a, a) == \
        ["hvd_adasum_norms_dot"]
    assert pallas_names(fused.fused_combine, a, a) == \
        ["hvd_adasum_norms_dot", "hvd_adasum_combine"]
