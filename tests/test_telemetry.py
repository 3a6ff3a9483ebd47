"""Run telemetry: registry, flight recorder, /metrics aggregation,
incident assembly, and the ≤1.02 overhead guard.

Reference parity: the reference's observability is the Timeline
(``horovod/common/timeline.cc``) plus stall-inspector log lines; this
suite pins the TPU rebuild's replacement surface (core/telemetry.py,
docs/telemetry.md): a Prometheus-text ``GET /metrics`` endpoint on the
elastic coordinator that survives crash-restart, and cross-rank flight
recorder dumps assembled into incident reports (the chaos-tier proof of
the latter lives in tests/test_integration_run.py).
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.core import telemetry as T
from horovod_tpu.elastic.service import CoordinatorClient, CoordinatorService
from horovod_tpu.runner import secret as _secret
from horovod_tpu.tools.telemetry import parse_prometheus, ring_to_chrome


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv(T.FLIGHT_DIR_ENV, raising=False)
    monkeypatch.delenv(T.ENABLE_ENV, raising=False)
    T.reset()
    yield
    T.reset()


# --- registry ---------------------------------------------------------------

def test_counters_gauges_and_labels():
    r = T.Registry()
    r.inc("hvd_steps_total", what="train")
    r.inc("hvd_steps_total", 2.0, what="train")
    r.inc("hvd_steps_total", what="eval")
    r.set_gauge("hvd_last_step", 7)
    snap = r.export()
    assert snap["c"]['hvd_steps_total{what="train"}'] == 3.0
    assert snap["c"]['hvd_steps_total{what="eval"}'] == 1.0
    assert snap["g"]["hvd_last_step"] == 7.0
    assert r.counter_value("hvd_steps_total", what="train") == 3.0
    assert r.gauge_value("hvd_last_step") == 7.0


def test_histogram_flattens_to_monotone_counters():
    r = T.Registry()
    for v in (0.003, 0.05, 0.05, 100.0):
        r.observe("hvd_step_seconds", v)
    c = r.export()["c"]
    # cumulative buckets, _sum, _count — all mergeable as plain counters
    assert c['hvd_step_seconds_bucket{le="0.005"}'] == 1.0
    assert c['hvd_step_seconds_bucket{le="0.1"}'] == 3.0
    assert c['hvd_step_seconds_bucket{le="+Inf"}'] == 4.0
    assert c["hvd_step_seconds_count"] == 4.0
    assert abs(c["hvd_step_seconds_sum"] - 100.103) < 1e-9


def test_series_cap_drops_not_grows():
    r = T.Registry(max_series=4)
    for i in range(100):
        r.inc("hvd_noise_total", shard=i)
    snap = r.export()
    kept = [k for k in snap["c"] if k.startswith("hvd_noise_total")]
    assert len(kept) == 4
    assert snap["c"]["hvd_telemetry_series_dropped_total"] == 96.0


def test_delta_export_is_dirty_only_and_cumulative():
    r = T.Registry()
    r.inc("a_total")
    first = r.export(dirty_only=True)
    assert first["c"] == {"a_total": 1.0}
    # nothing new: empty delta
    assert r.export(dirty_only=True) == {"c": {}, "g": {}}
    r.inc("a_total")
    r.inc("a_total")
    second = r.export(dirty_only=True)
    # CUMULATIVE value, not an increment: a lost push heals on the next
    assert second["c"] == {"a_total": 3.0}


def test_disabled_telemetry_is_a_noop(monkeypatch):
    monkeypatch.setenv(T.ENABLE_ENV, "0")
    T.reset()
    T.inc("hvd_x_total")
    T.record_event("anything")
    assert not T.enabled()
    assert T.export_delta() is None
    assert T.active().ring.events() == []
    assert T.dump_flight("reason") is None


# --- prometheus text: render + parse round-trip (tier-1 acceptance) ---------

def test_render_parse_round_trip_with_rollup():
    per_rank = {
        0: {"c": {'hvd_steps_total{what="t"}': 10.0}, "g": {"hvd_last_step": 9.0}},
        1: {"c": {'hvd_steps_total{what="t"}': 12.0}, "g": {"hvd_last_step": 11.0}},
    }
    text = T.render_prometheus(per_rank)
    parsed = parse_prometheus(text)
    assert parsed["samples"]['hvd_steps_total{rank="0",what="t"}'] == 10.0
    assert parsed["samples"]['hvd_steps_total{rank="1",what="t"}'] == 12.0
    # fleet rollup: counters summed across ranks, no rank label
    assert parsed["samples"]['hvd_steps_total{what="t"}'] == 22.0
    assert parsed["types"]["hvd_steps_total"] == "counter"
    assert parsed["types"]["hvd_last_step"] == "gauge"
    # strictness the round trip relies on
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all } {\n")


def test_label_escaping_survives_the_wire():
    r = T.Registry()
    r.inc("hvd_q_total", path='/we"ird\\path')
    text = T.render_prometheus({0: r.export()})
    parsed = parse_prometheus(text)
    assert sum(v for k, v in parsed["samples"].items()
               if k.startswith("hvd_q_total")) == 2.0  # per-rank + rollup


# --- flight recorder --------------------------------------------------------

def test_ring_is_bounded_and_ordered():
    ring = T.FlightRecorder(size=8)
    for i in range(50):
        ring.record("step_end", step=i)
    evs = ring.events()
    assert len(evs) == 8
    assert [e["step"] for e in evs] == list(range(42, 50))
    assert all(e["t"] > 0 for e in evs)


def test_dump_flight_atomic_and_rank_named(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_PROCESS_ID", "5")
    monkeypatch.setenv(T.FLIGHT_DIR_ENV, str(tmp_path))
    T.reset()
    T.record_event("step_end", step=3)
    path = T.dump_flight("watchdog_expiry")
    assert path == str(tmp_path / "flight_5.jsonl")
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["kind"] == "step_end" and lines[0]["step"] == 3
    assert lines[-1]["kind"] == "flight_dump"
    assert lines[-1]["reason"] == "watchdog_expiry"
    # no torn tmp files left behind
    assert list(tmp_path.glob("*.tmp.*")) == []
    dumps = T.load_flight_dumps(str(tmp_path))
    assert list(dumps) == [5] and dumps[5] == lines


def test_assemble_incident_lines_up_ranks(tmp_path):
    for rank in (0, 2):
        ring = T.FlightRecorder(16)
        for s in range(5):
            ring.record("step_end", step=s, rank=rank)
        ring.record("rescue", reason="peer died", rank=rank)
        ring.dump(str(tmp_path / f"flight_{rank}.jsonl"))
    path = T.assemble_incident(
        str(tmp_path), 3,
        journal_tail=[{"op": "failure", "host": "h1"}],
        coordinator_metrics={1: {"c": {}, "g": {"hvd_last_step": 4.0}}},
        failure={"generation": 1, "codes": {"h1": 137}}, tail=4)
    report = json.load(open(path))
    assert report["failure_seq"] == 3
    assert sorted(report["ranks"]) == ["0", "2"]
    for evs in report["ranks"].values():
        assert len(evs) == 4                      # tail honored
        assert any(e["kind"] == "rescue" for e in evs)
    # the victim (rank 1, never dumped) is still visible via the
    # coordinator's last pushed metrics
    assert report["coordinator_metrics"]["1"]["g"]["hvd_last_step"] == 4.0
    assert report["journal_tail"][0]["op"] == "failure"


def test_ring_to_chrome_spans_and_instants():
    ring = T.FlightRecorder(16)
    ring.record("step_begin", what="train_step")
    ring.record("step_end", what="train_step", step=1)
    ring.record("sentinel", verdict="skip", step=1)
    evs = ring_to_chrome(ring.events(), rank=2)
    phases = [e["ph"] for e in evs]
    assert phases == ["B", "E", "i", "M"]
    assert all(e.get("pid") == 2 for e in evs)
    assert evs[0]["name"] == "train_step"


# --- coordinator /metrics aggregation (tier-1 acceptance) -------------------

def _push_and_scrape(svc, key):
    client = CoordinatorClient(f"127.0.0.1:{svc.port}", key)
    assert client.push_metrics(
        0, {"c": {'hvd_steps_total{what="t"}': 10.0},
            "g": {"hvd_last_step": 9.0}})
    assert client.push_metrics(
        1, {"c": {'hvd_steps_total{what="t"}': 12.0},
            "g": {"hvd_last_step": 11.0}})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{svc.port}/metrics", timeout=10) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        return resp.read().decode()


def test_metrics_push_aggregate_and_crash_restart(tmp_path):
    """Workers push cumulative deltas; GET /metrics serves parseable
    per-rank + rollup samples; a crash-restarted coordinator replays the
    journal and serves the SAME metrics."""
    key = _secret.make_secret_key()
    journal = str(tmp_path / "coord.journal")
    svc = CoordinatorService(key, bind_host="127.0.0.1",
                             journal_path=journal)
    try:
        svc.update_world({"a": 2}, 2)
        text = _push_and_scrape(svc, key)
        parsed = parse_prometheus(text)
        assert parsed["samples"]['hvd_steps_total{rank="0",what="t"}'] == 10
        assert parsed["samples"]['hvd_steps_total{what="t"}'] == 22
        assert parsed["samples"]['hvd_last_step{rank="1"}'] == 11
        # cumulative merge: a later push overwrites, not adds
        client = CoordinatorClient(f"127.0.0.1:{svc.port}", key)
        assert client.push_metrics(
            0, {"c": {'hvd_steps_total{what="t"}': 15.0}, "g": {}})
        assert svc.metrics_snapshot()["0"]["c"][
            'hvd_steps_total{what="t"}'] == 15.0
        svc.simulate_crash()
    finally:
        svc.close()
    svc2 = CoordinatorService(key, bind_host="127.0.0.1",
                              journal_path=journal, restore=True)
    try:
        snap = svc2.metrics_snapshot()
        assert snap["0"]["c"]['hvd_steps_total{what="t"}'] == 15.0
        assert snap["1"]["g"]["hvd_last_step"] == 11.0
        parsed = parse_prometheus(svc2.metrics_text())
        assert parsed["samples"]['hvd_steps_total{what="t"}'] == 27
    finally:
        svc2.close()


def test_metrics_push_never_bumps_world_version(tmp_path):
    """Metrics are observability, not membership: pushes must not wake
    long-polls or advance version/failure_seq (frozen protocol)."""
    key = _secret.make_secret_key()
    svc = CoordinatorService(key, bind_host="127.0.0.1")
    try:
        svc.update_world({"a": 1}, 1)
        v0, f0 = svc.version, svc.failure_seq
        client = CoordinatorClient(f"127.0.0.1:{svc.port}", key)
        assert client.push_metrics(0, {"c": {"x_total": 1.0}, "g": {}})
        assert svc.version == v0 and svc.failure_seq == f0
    finally:
        svc.close()


def test_malformed_metrics_push_is_ignored(tmp_path):
    key = _secret.make_secret_key()
    svc = CoordinatorService(key, bind_host="127.0.0.1")
    try:
        client = CoordinatorClient(f"127.0.0.1:{svc.port}", key)
        # garbage payloads: server must neither crash nor record
        client._call("/metrics",
                     json.dumps({"rank": "not-an-int", "c": 5}).encode())
        client._call("/metrics", json.dumps({"no_rank": True}).encode())
        assert svc.metrics_snapshot() == {}
        assert client.push_metrics(3, {"c": {"ok_total": 1.0}, "g": {}})
        assert svc.metrics_snapshot()["3"]["c"]["ok_total"] == 1.0
    finally:
        svc.close()


# --- instrumentation seams --------------------------------------------------

def test_watchdog_heartbeat_publishes_registry_gauges():
    from horovod_tpu.core import watchdog
    hb = watchdog.monitor().heartbeat()
    reg = T.active().registry
    assert reg.gauge_value("hvd_heartbeat_steps_completed") == float(
        hb["steps_completed"])
    assert reg.gauge_value("hvd_heartbeat_in_flight") is not None


def test_step_span_records_ring_and_metrics():
    from horovod_tpu.core import watchdog
    mon = watchdog.monitor()
    with mon.step_span("unit_step"):
        pass
    reg = T.active().registry
    assert reg.counter_value("hvd_steps_total", what="unit_step") >= 1.0
    kinds = [e["kind"] for e in T.active().ring.events()]
    assert "step_begin" in kinds and "step_end" in kinds
    end = [e for e in T.active().ring.events()
           if e["kind"] == "step_end"][-1]
    assert end["what"] == "unit_step" and end["seconds"] >= 0.0


def test_grouped_allreduce_records_collective_issue_at_trace():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.collectives import ops

    before = T.active().registry.counter_value("hvd_collective_issues_total")
    tree = {"a": jnp.zeros(128, jnp.float32),
            "b": jnp.zeros(128, jnp.float32)}
    f = shard_map(lambda t: ops.grouped_allreduce(t, hvd.Sum),
                  mesh=hvd.mesh(), in_specs=P(), out_specs=P(),
                  check_vma=False)
    jax.jit(f).lower(tree)   # trace only: the record fires at trace time
    after = T.active().registry.counter_value("hvd_collective_issues_total")
    # >= because shard_map may trace the body more than once per lower
    assert after >= before + 1.0
    ev = [e for e in T.active().ring.events()
          if e["kind"] == "collective_issue"][-1]
    assert ev["tensors"] == 2 and ev["bytes"] == 2 * 128 * 4
    assert ev["buckets"] >= 1


def test_sentinel_verdicts_reach_registry_and_ring():
    from horovod_tpu.core.sentinel import Sentinel
    s = Sentinel()
    # one non-finite step -> skip verdict through _note()
    action = s.observe_finite(False, step=1)
    assert action.kind == "skip"
    reg = T.active().registry
    assert reg.counter_value("hvd_sentinel_verdicts_total",
                             kind="skip") == 1.0
    ev = [e for e in T.active().ring.events()
          if e["kind"] == "sentinel"][-1]
    assert ev["verdict"] == "skip" and ev["step"] == 1


def test_callback_loop_records_host_side_logs():
    from horovod_tpu.callbacks import CallbackLoop

    class _St:
        params = {}
        opt_state = {}

    loop = CallbackLoop(_St(), [])
    loop.batch_end(3, {"loss": 0.5, "device_thing": object()})
    evs = [e for e in T.active().ring.events() if e["kind"] == "batch_end"]
    assert evs and evs[-1]["loss"] == 0.5 and evs[-1]["index"] == 3
    assert "device_thing" not in evs[-1]   # non-scalars never recorded
    assert T.active().registry.gauge_value("hvd_loop_loss") == 0.5


# --- overhead guard (slow: excluded from tier-1) ----------------------------

@pytest.mark.slow
def test_telemetry_overhead_within_bound():
    """Telemetry-on vs telemetry-off A/B on the 8-virtual-device CPU
    mesh: the per-step cost is a handful of dict updates under one lock
    plus a ring append — the median of per-round ratios must stay ≤1.02
    (docs/telemetry.md overhead contract; same interleaved-rounds
    methodology as the sentinel guard in test_sentinel.py)."""
    import sys
    sys.path.insert(0, "benchmarks")
    import flax.linen as nn
    from jax.sharding import Mesh
    from common import slope_time_paired

    from horovod_tpu.core import watchdog
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
    images = jnp.asarray(rng.randn(B, 8, 8, 4).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=(B,)))
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), (hvd.RANK_AXIS,))

    mon = watchdog.monitor()

    def build(enabled):
        # Fresh model/state per arm: the step donates its state, so arms
        # must not share one (a donated buffer cannot be passed again).
        model = Wide()
        dopt = distributed(optax.sgd(0.1))
        state = create_train_state(model, jax.random.PRNGKey(0),
                                   images[:1], dopt, mesh=mesh1)
        step = make_train_step(model, dopt, _xent, mesh=mesh1,
                               axis_name=hvd.RANK_AXIS, sentinel=False)
        box = {"state": state}

        def fn(k):
            T.configure(enabled=enabled)
            for _ in range(k):
                with mon.step_span("bench_step"):
                    box["state"], loss = step(box["state"], images, labels)
            jax.block_until_ready(loss)
        return fn

    # Measured telemetry cost is ~35us/step against a ~38ms step (0.1%);
    # the windows are sized so per-round slope noise stays under the
    # 1.02 bound (8-step windows read 5-8% noise on this host).
    _slopes, rounds = slope_time_paired(
        {"off": build(False), "on": build(True)},
        s_short=6, s_long=24, rounds=9, return_rounds=True)
    ratios = sorted(r["on"] / r["off"] for r in rounds)
    median = ratios[len(ratios) // 2]
    assert median <= 1.02, f"telemetry overhead ratio {median:.4f}"
