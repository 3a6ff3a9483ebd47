"""Perf-attribution plane (ISSUE 11, docs/profiling.md).

Reference parity: the per-op ``horovod/common/timeline.cc`` record plus
the autotuner's measure-persist-compare loop. This suite pins the TPU
rebuild's replacement surface (tools/perf.py): the step-time budget over
synthetic xplane traces (umbrella/async traps honored, categories sum to
wall), the per-model MFU ratchet over ``perf_history.jsonl``, regression
diffs that NAME the category and op, the live ``hvd_step_*`` gauges
through the watchdog, and their coordinator ``/metrics`` fleet rollup.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from horovod_tpu.core import telemetry as T
from horovod_tpu.core import watchdog
from horovod_tpu.elastic.service import CoordinatorClient, CoordinatorService
from horovod_tpu.runner import secret as _secret
from horovod_tpu.tools import perf
from horovod_tpu.tools.telemetry import parse_prometheus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: 1 ms in picoseconds — xplane event durations are ps; the record schema
#: rounds seconds to 6 places, so synthetic events must be ms-scale.
MS = 10**9


@pytest.fixture(autouse=True)
def _fresh_perf(monkeypatch):
    monkeypatch.delenv(perf.HISTORY_ENV, raising=False)
    monkeypatch.delenv(perf.NO_HISTORY_ENV, raising=False)
    monkeypatch.delenv(perf.RATCHET_BAND_ENV, raising=False)
    T.reset()
    yield
    T.reset()


# --- synthetic xplane traces -------------------------------------------------

def _tpu_space():
    """One TPU core plane exercising every budget trap:

    lane (XLA Ops):  dot.1 [0,400) copy.2 [400,500) all-reduce.3 [500,700)
                     loop_fusion.5 [700,900)  + a %while.4 umbrella [0,700)
    XLA Modules:     one 1000 ms module (the wall source)
    Async XLA Ops:   a 300 ms all-reduce-start window overlapping compute
    """
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    plane = space.planes.add()
    plane.name = "/device:TPU:0 (pid 1)"
    names = {
        1: "%dot.1 = bf16[256,256]{1,0} dot(%p0, %p1)",
        2: "%copy.2",
        3: "%all-reduce.3",
        4: "%while.4",           # scan umbrella: covers its children
        5: "%loop_fusion.5",
        6: "jit_train_step",
        7: "%all-reduce-start.6",
    }
    for mid, nm in names.items():
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = nm

    def _ev(line, mid, start_ms, dur_ms):
        ev = line.events.add()
        ev.metadata_id = mid
        ev.offset_ps = start_ms * MS
        ev.duration_ps = dur_ms * MS

    modules = plane.lines.add()
    modules.name = "XLA Modules"
    _ev(modules, 6, 0, 1000)
    ops = plane.lines.add()
    ops.name = "XLA Ops"
    _ev(ops, 1, 0, 400)
    _ev(ops, 2, 400, 100)
    _ev(ops, 3, 500, 200)
    _ev(ops, 4, 0, 700)          # umbrella — must be dropped
    _ev(ops, 5, 700, 200)
    async_line = plane.lines.add()
    async_line.name = "Async XLA Ops"
    _ev(async_line, 7, 0, 300)   # overlap window — never occupancy
    return space


def _cpu_space():
    """A /host:CPU plane: thunk lanes carry bare HLO names; client-infra
    spans (spaces/colons) and the python line must not count."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    plane = space.planes.add()
    plane.name = "/host:CPU"
    names = {1: "dot.3", 2: "fusion.7", 3: "ExecuteHelper: run",
             4: "while.9", 5: "PyCall"}
    for mid, nm in names.items():
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = nm
    lane = plane.lines.add()
    lane.name = "thunk-executor 0"
    for mid, start, dur in ((1, 0, 400), (2, 600, 400),
                            (3, 0, 5000), (4, 0, 1000)):
        ev = lane.events.add()
        ev.metadata_id = mid
        ev.offset_ps = start * MS
        ev.duration_ps = dur * MS
    py = plane.lines.add()
    py.name = "python"
    ev = py.events.add()
    ev.metadata_id = 5
    ev.offset_ps = 0
    ev.duration_ps = 9999 * MS
    return space


def test_budget_sums_to_wall_and_honors_xplane_traps():
    b = perf.budget_from_space(_tpu_space())
    cat = b["cat_ps"]
    assert b["wall_ps"] == 1000 * MS          # from XLA Modules, not ops
    assert cat["matmul/conv"] == 400 * MS
    assert cat["copy/transpose"] == 100 * MS
    assert cat["elementwise"] == 200 * MS
    # the async window hides the on-lane collective: all 200 ms of
    # all-reduce occupancy intersect concurrent compute/DMA
    assert cat["collective_hidden"] == 200 * MS
    assert cat["collective_exposed"] == 0
    assert cat["gather/scatter"] == 0 and cat["other"] == 0
    assert cat["host_gap"] == 100 * MS
    # THE property: categories + gap partition the wall exactly
    assert sum(cat.values()) == b["wall_ps"]
    # %while umbrella dropped (counting it would double the step);
    # async window feeds only the hidden intersection, never occupancy
    assert "while.4" not in b["op_n"]
    assert "all-reduce-start.6" not in b["op_n"]
    assert b["hidden_ps"] == 300 * MS
    assert b["collective_total_ps"] == 200 * MS
    assert "all-reduce.3" in b["op_ps"]["collective_exposed"]


def test_cpu_plane_filters_infra_and_python_lines():
    b = perf.budget_from_space(_cpu_space())
    cat = b["cat_ps"]
    assert b["n_lanes"] == 1
    assert b["wall_ps"] == 1000 * MS          # lane extent of real ops
    assert cat["matmul/conv"] == 400 * MS
    assert cat["elementwise"] == 400 * MS
    assert cat["host_gap"] == 200 * MS
    assert sum(cat.values()) == b["wall_ps"]
    assert "ExecuteHelper: run" not in b["op_n"]   # client infra
    assert "while.9" not in b["op_n"]              # umbrella
    assert "PyCall" not in b["op_n"]               # python line


def test_attribute_logdir_record_schema(tmp_path):
    space = _tpu_space()
    (tmp_path / "t.xplane.pb").write_bytes(space.SerializeToString())
    rec = perf.attribute_logdir(str(tmp_path), 2, model="synth",
                                flops_per_step=4e9)
    assert rec["kind"] == "perf_budget" and rec["model"] == "synth"
    assert rec["wall_s_per_step"] == 0.5
    for key in perf.BUDGET_KEYS:
        assert key in rec["budget_s_per_step"], key
    assert rec["budget_s_per_step"]["matmul/conv"] == 0.2
    assert rec["sum_check"]["rel_err"] <= perf.SUM_TOLERANCE
    assert rec["top_ops"]["matmul/conv"][0]["op"] == "dot.1"
    # CPU device peak is unknown: throughput recorded, MFU omitted
    assert rec["achieved_tflops"] == pytest.approx(0.008)
    assert "mfu" not in rec


def test_categorize_budget_categories():
    cases = {"%dot.12 = f32[8,8] dot(...)": "matmul/conv",
             "convolution.3": "matmul/conv",
             "gather.1": "gather/scatter",
             "dynamic-slice.9": "gather/scatter",
             "%scatter-add.2": "gather/scatter",
             "copy.4": "copy/transpose",
             "transpose.8": "copy/transpose",
             "all-reduce.1": "collective",
             "reduce-scatter.2": "collective",
             "%loop_fusion.5": "elementwise",
             "wat.7": "other"}
    for name, want in cases.items():
        assert perf.categorize_budget(name) == want, name


# --- history + ratchet -------------------------------------------------------

def _rec(model, mfu=None, wall=0.1, rel_err=0.0, drop_key=None,
         budget=None, top_ops=None):
    b = dict(budget or {k: 0.0 for k in perf.BUDGET_KEYS})
    if drop_key:
        b.pop(drop_key)
    r = {"kind": "perf_budget", "metric": f"{model}_step_budget",
         "model": model, "steps": 1, "n_lanes": 1,
         "wall_s_per_step": wall, "budget_s_per_step": b,
         "sum_check": {"sum_s": wall, "wall_s": wall, "rel_err": rel_err},
         "top_ops": top_ops or {}}
    if mfu is not None:
        r["mfu"] = mfu
    return r


def test_history_round_trip_is_stamped(tmp_path, monkeypatch):
    hist = tmp_path / "perf.jsonl"
    monkeypatch.setenv(perf.HISTORY_ENV, str(hist))
    assert perf.append_history(_rec("m", mfu=0.4)) == str(hist)
    recs = perf.load_history()
    assert len(recs) == 1
    assert recs[0]["model"] == "m" and recs[0]["mfu"] == 0.4
    assert "date" in recs[0] and "git" in recs[0]   # provenance stamp
    ok, _ = perf.ratchet_check(recs)
    assert ok


def test_no_history_env_suppresses_append(tmp_path, monkeypatch):
    hist = tmp_path / "perf.jsonl"
    monkeypatch.setenv(perf.HISTORY_ENV, str(hist))
    monkeypatch.setenv(perf.NO_HISTORY_ENV, "1")
    assert perf.append_history(_rec("m")) is None
    assert not hist.exists()


def test_ratchet_wins_rail_the_floor_and_drops_fail():
    # a win ratchets the floor up; the next record is judged against it
    ok, msgs = perf.ratchet_check(
        [_rec("m", mfu=0.30), _rec("m", mfu=0.50), _rec("m", mfu=0.50)],
        band=0.9)
    assert ok and any("ok [m]" in m for m in msgs)
    # a drop below best*band fails even though it beats the FIRST record
    ok, msgs = perf.ratchet_check(
        [_rec("m", mfu=0.30), _rec("m", mfu=0.50), _rec("m", mfu=0.40)],
        band=0.9)
    assert not ok
    assert any("FAIL ratchet [m]" in m for m in msgs)


def test_ratchet_noise_band_warns_not_fails():
    ok, msgs = perf.ratchet_check(
        [_rec("m", mfu=0.50), _rec("m", mfu=0.47)], band=0.9)
    assert ok
    assert any(m.startswith("warn [m]") for m in msgs)


def _ratio_rec(model, arm, ratio):
    return {"kind": "perf_ratio", "metric": f"{model}_{arm}_ratio",
            "model": model, "arm": arm, "ratio": ratio,
            "noise": {"lo": ratio * 0.98, "hi": ratio * 1.02}}


def test_perf_ratio_records_rail_per_arm():
    # a measured A/B win becomes a per-(model, arm)
    # floor: later records inside the band pass, a collapse fails
    hist = [_ratio_rec("llama_tiny", "remat_none_vs_full", 1.30),
            _ratio_rec("llama_tiny", "remat_none_vs_full", 1.28)]
    ok, msgs = perf.ratchet_check(hist, band=0.9)
    assert ok
    assert any("warn [llama_tiny/remat_none_vs_full]" in m for m in msgs)
    ok, msgs = perf.ratchet_check(
        hist + [_ratio_rec("llama_tiny", "remat_none_vs_full", 1.0)],
        band=0.9)
    assert not ok
    assert any("FAIL ratchet [llama_tiny/remat_none_vs_full]" in m
               for m in msgs)
    # arms rail independently: one arm's drop does not hide behind
    # another arm's win on the same model
    ok, _ = perf.ratchet_check(
        hist + [_ratio_rec("llama_tiny", "scan_vs_unroll", 1.05)],
        band=0.9)
    assert ok


def test_perf_ratio_records_excluded_from_mfu_grouping():
    # a ratio record carries no MFU/budget — it must not drag a model
    # into (or pollute) the MFU ratchet, and a malformed one FAILs shape
    ok, msgs = perf.ratchet_check(
        [_rec("m", mfu=0.50), _ratio_rec("m", "accum4_vs_plain", 1.06)],
        band=0.9)
    assert ok
    assert any("ok [m]: MFU" in m for m in msgs)
    ok, msgs = perf.ratchet_check(
        [{"kind": "perf_ratio", "model": "m", "ratio": "fast"}])
    assert not ok and any("FAIL shape [perf_ratio]" in m for m in msgs)


def _spec_rec(arm, ratio, **over):
    rec = {"kind": "spec_decode", "metric": "spec_decode_speedup",
           "model": "llama_tiny_serve_cpu8", "arm": arm, "ratio": ratio,
           "spec_k": 4,
           "tokens_per_s": {"plain": 1600.0, "spec": 1600.0 * ratio},
           "noise": {"rounds": 6, "ratio_min": ratio * 0.9,
                     "ratio_max": ratio * 1.1, "spread": ratio * 0.2},
           "steady_compiles": 0}
    rec.update(over)
    return rec


def test_spec_decode_rails_absolute_floors_per_arm():
    # the ISSUE 16 rails are ABSOLUTE per workload arm, not best-ever:
    # repeat_heavy >= 1.5x plain, adversarial >= 0.9x plain
    ok, msgs = perf.ratchet_check(
        [_spec_rec("repeat_heavy", 2.4), _spec_rec("adversarial", 0.96)],
        band=0.9)
    assert ok
    assert any("ok [spec_decode" in m and "repeat_heavy" in m
               for m in msgs)
    ok, msgs = perf.ratchet_check([_spec_rec("adversarial", 0.85)],
                                  band=0.9)
    assert not ok and any("FAIL floor [spec_decode" in m for m in msgs)
    ok, msgs = perf.ratchet_check([_spec_rec("repeat_heavy", 1.3)],
                                  band=0.9)
    assert not ok and any("FAIL floor [spec_decode" in m for m in msgs)


def test_spec_decode_drift_below_best_warns_not_fails():
    # acceptance-driven medians swing wider than the MFU band
    # (measured 1.95-2.52 across honest sessions): below best*band but
    # above the absolute floor is a drift WARNING, not a failure
    ok, msgs = perf.ratchet_check(
        [_spec_rec("repeat_heavy", 2.5), _spec_rec("repeat_heavy", 1.95)],
        band=0.9)
    assert ok
    assert any("warn [spec_decode" in m for m in msgs)


def test_spec_decode_shape_rails():
    # zero steady-state compiles is part of the record's SHAPE: a spec
    # arm that recompiles mid-stream is broken even at a great ratio
    ok, msgs = perf.ratchet_check(
        [_spec_rec("repeat_heavy", 2.4, steady_compiles=1)])
    assert not ok and any("FAIL shape [spec_decode]" in m for m in msgs)
    for bad in (_spec_rec("warp_drive", 2.4),          # unknown arm
                _spec_rec("repeat_heavy", 2.4, spec_k=1),
                _spec_rec("repeat_heavy", 2.4, noise={"rounds": 2}),
                _spec_rec("repeat_heavy", 2.4,
                          tokens_per_s={"plain": 1600.0})):
        ok, msgs = perf.ratchet_check([bad])
        assert not ok and any("FAIL shape [spec_decode]" in m
                              for m in msgs)
    # spec records never join the MFU grouping
    ok, msgs = perf.ratchet_check(
        [_rec("m", mfu=0.5), _spec_rec("adversarial", 0.96)], band=0.9)
    assert ok
    assert any("ok [m]: MFU" in m for m in msgs)


def test_ratchet_band_env_is_honored(monkeypatch):
    monkeypatch.setenv(perf.RATCHET_BAND_ENV, "0.5")
    ok, _ = perf.ratchet_check([_rec("m", mfu=0.50), _rec("m", mfu=0.30)])
    assert ok      # 0.30 >= 0.50 * 0.5


def test_shape_rail_missing_category_and_sum_breach():
    ok, msgs = perf.ratchet_check([_rec("m", drop_key="host_gap")])
    assert not ok and any("FAIL shape" in m and "host_gap" in m
                          for m in msgs)
    ok, msgs = perf.ratchet_check([_rec("m", rel_err=0.2)])
    assert not ok and any("FAIL shape" in m and "rel_err" in m
                          for m in msgs)


def test_mfu_free_records_are_shape_railed_only():
    # CPU-mesh records carry no MFU (peak unknown): shape rail still
    # applies, the ratchet does not — and says so
    ok, msgs = perf.ratchet_check([_rec("cpu_model")])
    assert ok
    assert any("shape-railed only" in m for m in msgs)


# --- diff: name the category AND the op --------------------------------------

def _ab_records():
    keys = {k: 0.0 for k in perf.BUDGET_KEYS}
    a = _rec("synth", wall=0.080,
             budget={**keys, "matmul/conv": 0.050, "gather/scatter": 0.010},
             top_ops={"gather/scatter": [
                 {"op": "gather.7", "ms_per_step": 8.0, "share": 0.1,
                  "n": 4}]})
    b = _rec("synth", wall=0.102,
             budget={**keys, "matmul/conv": 0.052, "gather/scatter": 0.030},
             top_ops={"gather/scatter": [
                 {"op": "gather.7", "ms_per_step": 25.0, "share": 0.25,
                  "n": 4},
                 {"op": "scatter.9", "ms_per_step": 5.0, "share": 0.05,
                  "n": 2}]})
    return a, b


def test_diff_names_regressed_category_and_top_op():
    a, b = _ab_records()
    out = perf.diff_records(a, b)
    assert out["regressed_category"] == "gather/scatter"
    assert out["top_op"] == "gather.7"     # ranked by GROWTH, not size
    assert out["wall_delta_s_per_step"] == pytest.approx(0.022)
    assert out["category_deltas_s_per_step"]["gather/scatter"] == \
        pytest.approx(0.020)


def test_cli_show_and_diff(tmp_path, capsys):
    hist = tmp_path / "perf.jsonl"
    a, b = _ab_records()
    with open(hist, "w") as f:
        f.write(json.dumps(a) + "\n" + json.dumps(b) + "\n")
    assert perf.main(["--history", str(hist), "show"]) == 0
    assert "step budget [synth]" in capsys.readouterr().out
    assert perf.main(["--history", str(hist), "diff", "0", "1",
                      "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "perf_diff"
    assert out["regressed_category"] == "gather/scatter"
    assert out["top_op"] == "gather.7"
    # model:idx selectors hit the same records
    assert perf.main(["--history", str(hist), "diff", "synth:0",
                      "synth:-1", "--json"]) == 0


def test_cli_check_exit_codes(tmp_path, capsys):
    hist = tmp_path / "perf.jsonl"
    with open(hist, "w") as f:
        f.write(json.dumps(_rec("m", mfu=0.5)) + "\n")
    assert perf.main(["--history", str(hist), "check"]) == 0
    capsys.readouterr()
    with open(hist, "a") as f:
        f.write(json.dumps(_rec("m", mfu=0.3)) + "\n")
    assert perf.main(["--history", str(hist), "check", "--json"]) == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] is False
    assert any("FAIL ratchet" in m for m in out["messages"])
    # empty history is ok (fresh checkout, nothing recorded yet)
    assert perf.main(["--history", str(tmp_path / "none.jsonl"),
                      "check"]) == 0


def test_cli_subprocess_entry_point(tmp_path):
    """The operator-facing spelling: ``python -m horovod_tpu.tools.perf``
    must exit 1 on a ratchet breach (the CI rail's contract)."""
    hist = tmp_path / "perf.jsonl"
    with open(hist, "w") as f:
        f.write(json.dumps(_rec("m", mfu=0.5)) + "\n")
        f.write(json.dumps(_rec("m", mfu=0.3)) + "\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.tools.perf",
         "--history", str(hist), "check"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert out.returncode == 1, out.stdout + out.stderr[-1000:]
    assert "FAIL ratchet" in out.stdout


# --- device peak table ------------------------------------------------------

def test_device_peak_flops_table():
    class _Dev:
        device_kind = "TPU v5p"
    assert perf.device_peak_flops(_Dev()) == 459e12
    _Dev.device_kind = "weird accelerator"
    import math
    assert math.isnan(perf.device_peak_flops(_Dev()))


# --- live gauges through the watchdog ----------------------------------------

def test_step_span_sets_wall_gauge_and_leaves_data_wait_to_the_prefetcher():
    mon = watchdog.monitor()
    with mon.step_span("perf_span"):
        time.sleep(0.002)
    time.sleep(0.02)    # the loop fetches a loss, logs, ...: not data wait
    with mon.step_span("perf_span"):
        pass
    assert mon.monitored_call(lambda: 7, what="perf_span") == 7
    reg = T.active().registry
    wall = reg.gauge_value("hvd_step_wall_seconds", what="perf_span")
    assert wall is not None and wall >= 0.0
    # the gap between two step calls holds everything the user's loop does
    # between them; the monitor no longer calls it data wait. The gauge is
    # set where the wait happens, in Prefetcher.__next__, and nowhere else.
    assert reg.gauge_value("hvd_step_data_wait_seconds",
                           what="perf_span") is None
    assert reg.gauge_value("hvd_step_data_wait_seconds") is None
    from horovod_tpu.data import Prefetcher
    with Prefetcher(iter([np.zeros(4, np.float32)]),
                    transfer=lambda b: b) as feed:
        next(feed)      # accounted by the worker, or at close
    wait = reg.gauge_value("hvd_step_data_wait_seconds")
    assert wait is not None and 0.0 <= wait < 0.02


# --- coordinator /metrics fleet rollup ---------------------------------------

def test_metrics_endpoint_serves_step_gauges_with_mean_rollup():
    """GET /metrics must carry the hvd_step_* gauges per rank AND a fleet
    rollup line — gauges AVERAGE across ranks (a summed step-wall would
    read as a slowdown every time a worker joins)."""
    key = _secret.make_secret_key()
    svc = CoordinatorService(key, bind_host="127.0.0.1")
    try:
        client = CoordinatorClient(f"127.0.0.1:{svc.port}", key)
        assert client.push_metrics(0, {"c": {}, "g": {
            'hvd_step_wall_seconds{what="t"}': 0.1,
            'hvd_last_step': 4.0,
            'hvd_step_data_wait_seconds{what="t"}': 0.01}})
        assert client.push_metrics(1, {"c": {}, "g": {
            'hvd_step_wall_seconds{what="t"}': 0.3,
            'hvd_last_step': 6.0,
            'hvd_step_data_wait_seconds{what="t"}': 0.03}})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{svc.port}/metrics", timeout=10) as resp:
            text = resp.read().decode()
    finally:
        svc.close()
    s = parse_prometheus(text)["samples"]
    assert s['hvd_step_wall_seconds{rank="0",what="t"}'] == 0.1
    assert s['hvd_step_wall_seconds{rank="1",what="t"}'] == 0.3
    assert s['hvd_step_wall_seconds{what="t"}'] == pytest.approx(0.2)
    assert s['hvd_last_step'] == pytest.approx(5.0)
    assert s['hvd_step_data_wait_seconds{what="t"}'] == pytest.approx(0.02)
    assert parse_prometheus(text)["types"]["hvd_last_step"] == "gauge"


def test_render_rollup_averages_gauges_sums_counters():
    per_rank = {
        0: {"c": {"hvd_steps_total": 10.0},
            "g": {"hvd_step_wall_seconds": 0.2}},
        1: {"c": {"hvd_steps_total": 30.0},
            "g": {"hvd_step_wall_seconds": 0.4}},
    }
    s = parse_prometheus(T.render_prometheus(per_rank))["samples"]
    assert s["hvd_steps_total"] == 40.0                       # summed
    assert s["hvd_step_wall_seconds"] == pytest.approx(0.3)   # averaged


# --- overhead guard (slow: excluded from tier-1) -----------------------------

@pytest.mark.slow
def test_perf_gauges_overhead_within_bound():
    """Full perf instrumentation (the wall gauges and the step's life as
    spans: every batch comes through ``Prefetcher``,
    so a step carries ``input.produce``/``input.transfer`` on the worker and
    ``input.wait`` + ``step.dispatch``, the data-wait gauge and two counters
    on the loop's thread) vs telemetry-off A/B on the CPU mesh: median of
    per-round ratios ≤ 1.02 — the same bound and interleaved-rounds
    methodology as test_telemetry_overhead_within_bound."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh
    from common import slope_time_paired

    import horovod_tpu as hvd
    from horovod_tpu.data import Prefetcher
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    class Wide(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            for _ in range(3):
                x = nn.relu(nn.Dense(512)(x))
            return nn.Dense(10)(x)

    def _xent(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    rng = np.random.RandomState(0)
    B = 512
    host_images = rng.randn(B, 8, 8, 4).astype(np.float32)
    host_labels = rng.randint(0, 10, size=(B,))
    images = jnp.asarray(host_images)
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), (hvd.RANK_AXIS,))
    mon = watchdog.monitor()
    feeds = []

    def host_batches():
        while True:
            yield host_images, host_labels

    def build(instrumented):
        model = Wide()
        dopt = distributed(optax.sgd(0.1))
        state = create_train_state(model, jax.random.PRNGKey(0),
                                   images[:1], dopt, mesh=mesh1)
        step = make_train_step(model, dopt, _xent, mesh=mesh1,
                               axis_name=hvd.RANK_AXIS, sentinel=False)
        box = {"state": state}
        feed = Prefetcher(host_batches(), mesh=mesh1, axis=hvd.RANK_AXIS)
        feeds.append(feed)

        def fn(k):
            if instrumented:
                T.configure(enabled=True)
            else:
                T.configure(enabled=False)
            for _ in range(k):
                x, y = next(feed)
                with mon.step_span("bench_step"):
                    box["state"], loss = step(box["state"], x, y)
            jax.block_until_ready(loss)
        return fn

    try:
        _slopes, rounds = slope_time_paired(
            {"off": build(False), "on": build(True)},
            s_short=6, s_long=24, rounds=9, return_rounds=True)
    finally:
        for feed in feeds:
            feed.close()
    assert T.spans("input.wait") and T.spans("step.dispatch")
    ratios = sorted(r["on"] / r["off"] for r in rounds)
    median = ratios[len(ratios) // 2]
    assert median <= 1.02, f"perf gauge overhead ratio {median:.4f}"
