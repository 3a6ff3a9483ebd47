"""Fleet harness (ISSUE 19): a diurnal traffic trace against the WHOLE
stack at once — real CoordinatorService (journaled), real
InferenceServer replicas joined through ReplicaAgent, a FleetArbiter
re-bidding hosts between training and serving under hysteresis, a
training arm running a real jitted step loop credited with whatever
``training_np`` the arbiter leaves it, and FleetClient traffic with
failover/shed semantics.

The trace is a sinusoid starting at its trough: offered QPS =
``base + amp * sin(2π(t - period/4)/period)``, so the run opens below
one replica's capacity (the arbiter holds serving at its floor and
training keeps most hosts), climbs past it mid-period (queue depth
sustains past ``queue_high``, the arbiter grows serving, the fleet
spawns a replica), and falls back (drain + host returned to training).
Per-item service time is a fixed ``sleep`` inside the forward — the
knob that makes one replica's capacity known, so the trace provably
crosses it. A ``traffic_spike`` fault (testing/faults.py, ``req=``
axis) multiplies the offered rate when ``HOROVOD_FAULT_SPEC`` is set —
the chaos tier's hook; the committed record runs the plain sinusoid.

What one committed record (``benchmarks/fleet_history.jsonl``) holds:

- ``served_qps`` / ``shed_fraction`` / ``failed`` — every request is
  answered, shed with 429 (surfacing as FleetOverloadedError), or a
  FAILURE; the rails demand failed == 0 and a shed-fraction ceiling.
- ``p99_staleness_s`` — commit→served lag sampled on every live
  replica while a publisher commits+publishes+announces on a cadence
  mid-traffic (hot-swaps land THROUGH the trace, not around it).
- ``training.throughput_retained`` — trace-window samples/s (each step
  credits the arbiter's current ``training_np``; the graceful-reset
  enactment itself is covered by the elastic tests) over a pre-trace
  baseline at full ``total_hosts``.
- ``steady_compiles`` — the serving forward and the training step are
  both jitted with fixed bucket shapes; after warmup their jit caches
  must not grow (zero steady-state recompiles, the same contract the
  decode bench rails).
- ``arbiter`` — decision count, the journal-REPLAYED arbiter seq and
  fleet shape (must match the live ones: every decision is an
  ``op:"arbiter"`` journal record — folded through compaction — the
  crash-replay substrate tests/test_fleet_chaos.py SIGKILLs), and the
  serving min/max the trace actually visited.

Emits one JSON line and appends it — stamped
with date + git SHA — unless ``HOROVOD_FLEET_NO_HISTORY`` is set.
``--check`` validates the newest committed record against the rails;
``--smoke`` runs a shrunk trace for the chaos tier.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np                                             # noqa: E402

from benchmarks import common  # noqa: E402,F401  (forces cpu backend)
from horovod_tpu.checkpoint.store import BlobStore             # noqa: E402
from horovod_tpu.elastic.arbiter import (ArbiterPolicy,        # noqa: E402
                                         FleetArbiter)
from horovod_tpu.elastic.service import (CoordinatorClient,    # noqa: E402
                                         CoordinatorService)
from horovod_tpu.elastic.state import ObjectState              # noqa: E402
from horovod_tpu.runner import secret as _secret               # noqa: E402
from horovod_tpu.serving import (InferenceServer,              # noqa: E402
                                 ModelRegistry, Publisher)
from horovod_tpu.serving.fleet import (FleetClient,            # noqa: E402
                                       FleetOverloadedError,
                                       FleetRequestError, ReplicaAgent)

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fleet_history.jsonl")
NO_HISTORY_ENV = "HOROVOD_FLEET_NO_HISTORY"

#: --check rails (ISSUE 19 acceptance). The QPS floor sits far under
#: the trace mean so only a real serving collapse can cross it; the
#: shed ceiling is the overload-containment contract (shedding is
#: DEGRADATION, a shed storm is a regression); the retained floor is
#: the analytic minimum (arbiter may hold training at 1/4 hosts for
#: part of the trace) with contention slack; staleness is railed at a
#: few publish cadences so a stuck adoption path cannot hide.
MIN_SERVED_QPS = 8.0
MAX_SHED_FRACTION = 0.25
MAX_P99_STALENESS_S = 5.0
MIN_TRAINING_RETAINED = 0.2

BUCKETS = (1, 2, 4, 8)
SERVING_RANK0 = 901


def _counters_clean() -> Dict[str, int]:
    return {"steps_skipped": 0, "rollbacks": 0}


# -- the serving forward (shared jit cache across replicas) -------------------


def make_forward(service_s: float):
    """(forward, cache_size) — one jitted affine head shared by every
    replica so the compile accounting is one cache. The per-item sleep
    is the modeled service time that gives a replica a KNOWN capacity
    (~1/service_s items/s) for the trace to cross."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _affine(w, x):
        return x * w[0] + w[1]

    def forward(payload, inputs, padded_n):
        x = np.zeros(padded_n, np.float32)
        for i, q in enumerate(inputs):
            x[i] = float(q.get("x", 0.0))
        y = np.asarray(_affine(jnp.asarray(payload["attrs"]["w"]),
                               jnp.asarray(x)))
        time.sleep(service_s * len(inputs))
        return [float(v) for v in y[:len(inputs)]]

    # Warm every bucket: steady-state serving must never compile.
    w0 = jnp.zeros(2, jnp.float32)
    for b in BUCKETS:
        _affine(w0, jnp.zeros(b, jnp.float32)).block_until_ready()
    return forward, _affine._cache_size


# -- one replica: server + agent + real-signal pump ---------------------------


class _Replica:
    """A real InferenceServer joined to the fleet through ReplicaAgent,
    plus a pump thread pushing its REAL queue depth and staleness to
    the coordinator (in-process replicas share one telemetry registry,
    so the agent's own export_delta cannot keep them separable — the
    pump reads each server's actual queue instead)."""

    def __init__(self, service, key, store_dir: str, forward, rank: int,
                 stale_samples: List[float], lock: threading.Lock):
        self.rank = rank
        self.registry = ModelRegistry(
            store=BlobStore(os.path.join(store_dir, "cas")))
        self.server = InferenceServer(self.registry, forward,
                                      buckets=BUCKETS, window_s=0.004,
                                      request_timeout_s=10.0, rank=rank)
        self.client = CoordinatorClient(f"127.0.0.1:{service.port}", key,
                                        watch_publish=True)
        self.agent = ReplicaAgent(self.server, self.client,
                                  replica_id=f"bench-{rank}", rank=rank)
        self._stale_samples = stale_samples
        self._lock = lock
        self._stop = threading.Event()
        self.agent.start()
        self._pump_thread = threading.Thread(target=self._pump,
                                             daemon=True)
        self._pump_thread.start()

    def wait_ready(self, timeout_s: float = 15.0) -> bool:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if self.registry.current() is not None:
                return True
            time.sleep(0.02)
        return False

    def _push(self, depth: float, stale: Optional[float]) -> None:
        g = {"hvd_serving_queue_depth": depth}
        if stale is not None:
            g["hvd_serving_staleness_seconds"] = stale
        try:
            self.client.push_metrics(self.rank, {"g": g})
        except Exception:   # noqa: BLE001 — a dropped push heals next round
            pass

    def _pump(self) -> None:
        while not self._stop.is_set():
            stale = self.registry.staleness_s()
            if stale is not None:
                with self._lock:
                    self._stale_samples.append(stale)
            self._push(float(self.server._queue.qsize()), stale)
            self._stop.wait(0.1)
        # Zero the gauges on the way out so a drained replica's last
        # pushed depth cannot keep feeding the arbiter's max().
        self._push(0.0, 0.0)

    def drain_and_close(self, timeout_s: float = 15.0) -> None:
        self.agent.drain(timeout_s=timeout_s)
        self._stop.set()
        self._pump_thread.join(timeout=5)
        self.server.close()

    def close(self) -> None:
        self._stop.set()
        self.agent.close(deregister=True)
        self.server.close()


# -- the harness --------------------------------------------------------------


def run_harness(*, duration_s: float = 30.0, period_s: float = 12.0,
                base_qps: float = 25.0, amp_qps: float = 18.0,
                service_s: float = 0.03, publish_cadence_s: float = 1.0,
                total_hosts: int = 4, driver_threads: int = 12,
                baseline_s: float = 2.5) -> dict:
    from horovod_tpu.serving import constants as SC

    faulted = bool(os.environ.get("HOROVOD_FAULT_SPEC"))
    # A bounded queue is the point: 8 pending at ~service_s each keeps
    # worst-case queue wait well under a second, and the overload peak
    # actually sheds instead of buffering unboundedly. The drivers are
    # closed-loop (each thread waits its reply), so queue depth is
    # bounded by in-flight concurrency: driver_threads must exceed
    # queue_max or neither the arbiter's queue_high nor the shed bound
    # is reachable and the whole trace degenerates to self-throttling.
    overrides = {SC.QUEUE_MAX_ENV: "8", SC.SHED_RETRY_AFTER_ENV: "0.5"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        with tempfile.TemporaryDirectory(prefix="hvd_fleet_bench_") as d:
            return _run_in_dir(d, duration_s=duration_s,
                               period_s=period_s, base_qps=base_qps,
                               amp_qps=amp_qps, service_s=service_s,
                               publish_cadence_s=publish_cadence_s,
                               total_hosts=total_hosts,
                               driver_threads=driver_threads,
                               baseline_s=baseline_s, faulted=faulted)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_in_dir(d: str, *, duration_s, period_s, base_qps, amp_qps,
                service_s, publish_cadence_s, total_hosts,
                driver_threads, baseline_s, faulted) -> dict:
    import jax
    import jax.numpy as jnp

    key = _secret.make_secret_key()
    journal = os.path.join(d, "wal.jsonl")
    service = CoordinatorService(key, bind_host="127.0.0.1",
                                 journal_path=journal)
    admin = CoordinatorClient(f"127.0.0.1:{service.port}", key)

    # First generation published + announced before any replica starts.
    state = ObjectState(commit_dir=d, commit_async=False,
                        w=np.array([2.0, 3.0], np.float32))
    pub = Publisher(d, every=1, counters=_counters_clean)
    state.commit()
    rec0 = pub.maybe_publish(state._commit_seq)
    assert rec0 is not None and admin.announce_publish(rec0)

    forward, serve_cache_size = make_forward(service_s)

    # Training arm: a real jitted SGD loop on a fixed shape; each
    # dispatch runs K_INNER steps inside one XLA program (a bare
    # microstep-per-dispatch loop hammers the GIL ~40k times/s and
    # convoys every serving thread in this process — measured as
    # multi-second adoption stalls) and credits the arbiter's CURRENT
    # training_np (the multi-process graceful-reset enactment is
    # covered by the elastic tests — here the hosts the arbiter leaves
    # training are the accounting unit).
    K_INNER = 50

    @jax.jit
    def train_k(w, x, y):
        def body(_, w):
            def loss(w):
                return jnp.mean((x @ w - y) ** 2)
            return w - 0.01 * jax.grad(loss)(w)
        return jax.lax.fori_loop(0, K_INNER, body, w)

    tx = jnp.asarray(np.random.RandomState(0).randn(128, 64), jnp.float32)
    ty = jnp.asarray(np.random.RandomState(1).randn(128), jnp.float32)
    tw = jnp.zeros(64, jnp.float32)
    train_k(tw, tx, ty).block_until_ready()         # warm compile
    serve_warm = serve_cache_size()
    train_warm = train_k._cache_size()

    policy = ArbiterPolicy(queue_high=4.0, queue_low=1.0,
                           staleness_high_s=0.0, min_training_np=1,
                           min_replicas=1,
                           max_replicas=max(1, total_hosts - 1),
                           cooldown_s=2.0, sustain=2)
    arb = FleetArbiter(service, total_hosts=total_hosts, policy=policy)

    stale_samples: List[float] = []
    stale_lock = threading.Lock()
    fleet_lock = threading.Lock()
    replicas: List[_Replica] = []
    spawned = drained = 0

    def spawn_replica() -> None:
        nonlocal spawned
        r = _Replica(service, key, d, forward,
                     SERVING_RANK0 + spawned, stale_samples, stale_lock)
        r.wait_ready()
        with fleet_lock:
            replicas.append(r)
        spawned += 1

    for _ in range(arb.shape["serving_target"]):
        spawn_replica()

    stop = threading.Event()
    decisions: List[dict] = []
    drain_threads: List[threading.Thread] = []

    def arbiter_loop() -> None:
        nonlocal drained
        while not stop.is_set():
            dres = arb.evaluate()
            if dres is not None:
                decisions.append(dres)
                with fleet_lock:
                    have = len(replicas)
                want = dres["serving_target"]
                if want > have:
                    for _ in range(want - have):
                        spawn_replica()
                elif want < have:
                    for _ in range(have - want):
                        with fleet_lock:
                            victim = replicas.pop()
                        drained += 1
                        t = threading.Thread(
                            target=victim.drain_and_close, daemon=True)
                        t.start()
                        drain_threads.append(t)
            stop.wait(0.25)

    train_steps = 0
    train_samples = 0.0
    baseline_rate = [0.0]

    def training_loop() -> None:
        nonlocal train_steps, train_samples, tw
        # Pre-trace baseline: full total_hosts for baseline_s.
        t0, steps0 = time.perf_counter(), 0
        while time.perf_counter() - t0 < baseline_s:
            tw = train_k(tw, tx, ty)
            tw.block_until_ready()
            steps0 += K_INNER
        baseline_rate[0] = steps0 * total_hosts / (time.perf_counter() - t0)
        baseline_done.set()
        while not stop.is_set():
            tw = train_k(tw, tx, ty)
            tw.block_until_ready()
            train_steps += K_INNER
            train_samples += arb.shape["training_np"] * K_INNER

    baseline_done = threading.Event()
    publishes = [0]

    def publisher_loop() -> None:
        pclient = CoordinatorClient(f"127.0.0.1:{service.port}", key)
        while not stop.is_set():
            state.w = state.w + np.float32(1.0)
            state.commit()
            rec = pub.maybe_publish(state._commit_seq)
            if rec is not None and pclient.announce_publish(rec):
                publishes[0] += 1
            stop.wait(publish_cadence_s)

    # -- the diurnal drivers --------------------------------------------------

    counts = {"attempted": 0, "served": 0, "shed": 0, "failed": 0}
    counts_lock = threading.Lock()
    req_n = [0]
    spike = {"factor": 1.0, "until": 0.0}
    trace_t0 = [0.0]

    def offered_qps(now: float) -> float:
        t = now - trace_t0[0]
        qps = base_qps + amp_qps * math.sin(
            2 * math.pi * (t - period_s / 4) / period_s)
        if faulted and now < spike["until"]:
            qps *= spike["factor"]
        return max(0.5, qps)

    def driver_loop() -> None:
        fc = FleetClient(coord=CoordinatorClient(
            f"127.0.0.1:{service.port}", key), timeout_s=10.0,
            refresh_s=0.25, max_tries=10)
        while not stop.is_set():
            with counts_lock:
                n = req_n[0]
                req_n[0] += 1
            if faulted:
                from horovod_tpu.testing import faults as _faults
                f = _faults.on_traffic_request(n)
                if f is not None:
                    spike["factor"] = float(f.params.get("factor", 4))
                    spike["until"] = time.perf_counter() + float(
                        f.params.get("seconds", 2))
            t0 = time.perf_counter()
            try:
                out = fc.predict({"x": float(n)})
                ok = bool(out.get("ok"))
                with counts_lock:
                    counts["attempted"] += 1
                    counts["served" if ok else "failed"] += 1
            except FleetOverloadedError as e:
                with counts_lock:
                    counts["attempted"] += 1
                    counts["shed"] += 1
                time.sleep(min(e.retry_after_s, 0.25))
            except FleetRequestError:
                with counts_lock:
                    counts["attempted"] += 1
                    counts["failed"] += 1
            wall = time.perf_counter() - t0
            pause = driver_threads / offered_qps(time.perf_counter()) - wall
            if pause > 0:
                stop.wait(min(pause, 0.5))

    threads = [threading.Thread(target=fn, daemon=True, name=name)
               for name, fn in (("hvd-bench-arbiter", arbiter_loop),
                                ("hvd-bench-train", training_loop),
                                ("hvd-bench-pub", publisher_loop))]
    drivers = [threading.Thread(target=driver_loop, daemon=True,
                                name=f"hvd-bench-driver-{i}")
               for i in range(driver_threads)]
    serving_seen: List[int] = []
    try:
        for t in threads:
            t.start()
        assert baseline_done.wait(timeout=baseline_s * 20 + 30), \
            "training baseline never completed"
        trace_t0[0] = time.perf_counter()
        steps_at_trace = train_steps
        samples_at_trace = train_samples
        for t in drivers:
            t.start()
        deadline = trace_t0[0] + duration_s
        while time.perf_counter() < deadline:
            serving_seen.append(arb.shape["serving_target"])
            time.sleep(0.2)
        trace_wall = time.perf_counter() - trace_t0[0]
        trace_steps = train_steps - steps_at_trace
        trace_samples = train_samples - samples_at_trace
    finally:
        stop.set()
        for t in drivers + threads:
            t.join(timeout=30)
        for t in drain_threads:
            t.join(timeout=30)
        with fleet_lock:
            live = list(replicas)
        for r in live:
            r.close()

    # Replay, don't count raw lines: metrics pushes are journaled too,
    # so the journal compacts mid-trace and early arbiter records fold
    # into the snapshot. The replayed arbiter_seq/fleet IS the
    # crash-restart contract (what tests/test_fleet_chaos.py proves).
    from horovod_tpu.elastic import journal as journal_mod
    replayed = journal_mod.replay(journal) or {}
    view = service.fleet_view()
    service.close()

    retained = (trace_samples / trace_wall) / max(baseline_rate[0], 1e-9)
    with stale_lock:
        stales = sorted(stale_samples)
    attempted = max(counts["attempted"], 1)
    return {
        "bench": "fleet",
        "trace": {"duration_s": round(trace_wall, 2),
                  "period_s": period_s, "base_qps": base_qps,
                  "amp_qps": amp_qps, "service_s_per_item": service_s,
                  "publish_cadence_s": publish_cadence_s,
                  "driver_threads": driver_threads,
                  "faulted": faulted},
        "total_hosts": total_hosts,
        "requests": dict(counts),
        "served_qps": round(counts["served"] / trace_wall, 2),
        "shed_fraction": round(counts["shed"] / attempted, 4),
        "p99_staleness_s": round(
            float(np.percentile(stales, 99)), 4) if stales else None,
        "staleness_samples": len(stales),
        "publishes": publishes[0],
        "training": {
            "baseline_samples_per_s": round(baseline_rate[0], 1),
            "trace_samples_per_s": round(trace_samples / trace_wall, 1),
            "throughput_retained": round(retained, 4),
            "trace_steps": trace_steps,
        },
        "arbiter": {
            "decisions": len(decisions),
            "journal_arbiter_seq": replayed.get("arbiter_seq"),
            "journal_fleet": replayed.get("fleet"),
            "final_seq": view["arbiter_seq"],
            "final_shape": view["fleet"],
            "serving_min": min(serving_seen) if serving_seen else None,
            "serving_max": max(serving_seen) if serving_seen else None,
        },
        "replicas": {"spawned": spawned, "drained": drained},
        "steady_compiles": {
            "serving": serve_cache_size() - serve_warm,
            "training": train_k._cache_size() - train_warm,
        },
    }


def _append_history(rec: dict) -> None:
    import datetime
    import subprocess
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=os.path.dirname(HISTORY_PATH)
                             ).stdout.strip() or None
    except OSError:
        sha = None
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    with open(HISTORY_PATH, "a", encoding="utf-8") as fh:
        fh.write(json.dumps({"date": stamp, "git": sha, **rec}) + "\n")


# -- --check: guardrail over the recorded series ------------------------------


def check_history(path: str = HISTORY_PATH) -> dict:
    """Validate the NEWEST committed record against the ISSUE 19 rails:
    served-QPS floor, shed-fraction ceiling, zero failures, p99
    staleness ceiling, training-throughput-retained floor, zero
    steady-state recompiles, and decision/journal parity."""
    with open(path, "r", encoding="utf-8") as fh:
        recs = [json.loads(line) for line in fh if line.strip()]
    recs = [r for r in recs if r.get("bench") == "fleet"]
    if not recs:
        raise ValueError(f"no fleet records in {path}")
    rec = recs[-1]
    problems: List[str] = []

    def need(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)

    reqs = rec.get("requests") or {}
    need(reqs.get("attempted", 0) > 0 and reqs.get("served", 0) > 0,
         f"no traffic recorded: {reqs}")
    need(reqs.get("failed") == 0,
         f"requests FAILED (the never-hangs-never-500s contract): {reqs}")
    qps = rec.get("served_qps")
    need(isinstance(qps, (int, float)) and qps >= MIN_SERVED_QPS,
         f"served_qps={qps} < {MIN_SERVED_QPS}")
    shed = rec.get("shed_fraction")
    need(isinstance(shed, (int, float)) and 0 <= shed <= MAX_SHED_FRACTION,
         f"shed_fraction={shed} outside [0, {MAX_SHED_FRACTION}]")
    p99 = rec.get("p99_staleness_s")
    need(isinstance(p99, (int, float)) and 0 < p99 < MAX_P99_STALENESS_S,
         f"p99_staleness_s={p99} outside (0, {MAX_P99_STALENESS_S})")
    need(rec.get("staleness_samples", 0) >= 50,
         f"too few staleness samples: {rec.get('staleness_samples')}")
    need(rec.get("publishes", 0) >= 3,
         f"publish cadence did not run through the trace: "
         f"{rec.get('publishes')} publishes")
    tr = rec.get("training") or {}
    ret = tr.get("throughput_retained")
    need(isinstance(ret, (int, float)) and ret >= MIN_TRAINING_RETAINED,
         f"training throughput_retained={ret} < {MIN_TRAINING_RETAINED}")
    need(tr.get("trace_steps", 0) > 0,
         f"training arm idle during the trace: {tr}")
    arb = rec.get("arbiter") or {}
    need(arb.get("decisions", 0) >= 2,
         f"trace did not exercise a rebalance: {arb.get('decisions')} "
         f"decisions")
    need(arb.get("journal_arbiter_seq") == arb.get("decisions")
         and arb.get("journal_arbiter_seq") == arb.get("final_seq"),
         f"decision/journal parity broken: {arb}")
    jfleet = arb.get("journal_fleet") or {}
    shape = arb.get("final_shape") or {}
    need({k: jfleet.get(k) for k in ("serving_target", "training_np")}
         == {k: shape.get(k) for k in ("serving_target", "training_np")},
         f"journal-replayed fleet != live fleet: {jfleet} vs {shape}")
    need(shape.get("serving_target", 0) + shape.get("training_np", 0)
         == rec.get("total_hosts"),
         f"final shape does not cover total_hosts: {shape}")
    need((arb.get("serving_max") or 0) > (arb.get("serving_min") or 0),
         f"serving target never moved: {arb}")
    compiles = rec.get("steady_compiles") or {}
    need(compiles.get("serving") == 0 and compiles.get("training") == 0,
         f"steady-state recompiles in the fleet arms: {compiles}")
    return {"check": "fleet", "ok": not problems,
            "record_date": rec.get("date"), "record_git": rec.get("git"),
            "problems": problems}


# -- entry points -------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=30.0,
                    help="trace seconds (>= 2 diurnal periods default)")
    ap.add_argument("--period", type=float, default=12.0,
                    help="diurnal period seconds")
    ap.add_argument("--check", action="store_true",
                    help="validate the newest history record and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="shrunk trace, no history (chaos tier)")
    a = ap.parse_args(argv)

    if a.check:
        verdict = check_history()
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1

    if a.smoke:
        rec = run_harness(duration_s=8.0, period_s=6.0, baseline_s=1.0)
        print(json.dumps(rec))
        ok = (rec["requests"]["failed"] == 0
              and rec["requests"]["served"] > 0)
        return 0 if ok else 1

    rec = run_harness(duration_s=a.duration, period_s=a.period)
    print(json.dumps(rec))
    if os.environ.get(NO_HISTORY_ENV, "").lower() not in ("1", "true"):
        _append_history(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
