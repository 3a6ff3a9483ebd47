"""Fleet chaos (ISSUE 19 acceptance, np=3, marked slow): the two
crash proofs the unit layer cannot give.

1. Coordinator SIGKILL mid-rebalance: a subprocess coordinator hosts a
   FleetArbiter oscillating the fleet shape under a synthetic overload/
   drain cycle, confirming each decision to a side file only AFTER the
   journal append returned. The parent SIGKILLs it mid-stream, replays
   the journal into a fresh coordinator, and proves (a) every CONFIRMED
   decision is in the journal verbatim (fsync-per-record — nothing
   acknowledged is lost), (b) the replayed fleet shape IS the last
   journaled decision, and (c) a new arbiter seeded from the replay
   continues the same rebalance at seq+1.

2. replica_kill / replica_hang mid-traffic: three REAL replica
   subprocesses (InferenceServer + ReplicaAgent, registered through the
   coordinator) serve a published model; the victim carries
   ``HOROVOD_FAULT_SPEC`` so the fault harness SIGKILLs (or wedges) it
   on its Nth admitted request. A FleetClient drives traffic through
   the coordinator's /replicas list: every accepted request completes
   via failover — no hangs, no 500s surfacing, no lost answers.

The in-process (fake-clock, fast) versions of these behaviors live in
tests/test_fleet.py; this file is the subprocess ground truth.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from horovod_tpu.elastic import constants as C
from horovod_tpu.elastic import journal as journal_mod
from horovod_tpu.elastic.arbiter import ArbiterPolicy, FleetArbiter
from horovod_tpu.elastic.service import CoordinatorClient, CoordinatorService
from horovod_tpu.runner import secret as _secret
from horovod_tpu.serving import Publisher
from horovod_tpu.elastic.state import ObjectState
from horovod_tpu.serving.fleet import FleetClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.slow, pytest.mark.integration]


def _sub_env(tmp_path, **extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env["HOROVOD_FAULT_MARKER_DIR"] = str(tmp_path / "fault_markers")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _wait_for(pred, timeout=60, what="condition", proc=None):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        if proc is not None and proc.poll() is not None:
            out, err = proc.communicate(timeout=30)
            raise AssertionError(
                f"subprocess died waiting for {what}: "
                f"{out[-2000:]}\n{err[-2000:]}")
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


# ------------------------------------------- coordinator SIGKILL replay

ARBITER_VICTIM = """
import json
import os
import time
from horovod_tpu.elastic.arbiter import ArbiterPolicy, FleetArbiter
from horovod_tpu.elastic.service import CoordinatorService

key = bytes.fromhex(os.environ["KEY_HEX"])
svc = CoordinatorService(key, bind_host="127.0.0.1",
                         journal_path=os.environ["JOURNAL"])
policy = ArbiterPolicy(queue_high=10.0, queue_low=1.0, staleness_high_s=0,
                       min_training_np=1, min_replicas=1, max_replicas=6,
                       cooldown_s=0.0, sustain=1)
arb = FleetArbiter(svc, total_hosts=8, policy=policy)
dec_path = os.environ["DECISIONS"]
t, direction = 0.0, "up"
while True:
    serving = arb.shape["serving_target"]
    if direction == "up" and serving >= policy.max_replicas:
        direction = "down"
    elif direction == "down" and serving <= policy.min_replicas:
        direction = "up"
    q = 99.0 if direction == "up" else 0.0
    svc._record_metrics({"rank": 901,
                         "g": {"hvd_serving_queue_depth": q}})
    d = arb.evaluate(now=t)
    t += 1.0
    if d is not None:
        # CONFIRM only after record_arbiter_decision returned: anything
        # in this file must survive the SIGKILL via the journal.
        with open(dec_path, "a") as f:
            f.write(json.dumps(d) + "\\n")
            f.flush()
            os.fsync(f.fileno())
    time.sleep(0.02)
"""


def _journal_arbiter_records(path):
    out = {}
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue        # torn trailing line from the SIGKILL
            if rec.get("op") == "arbiter":
                out[int(rec["seq"])] = (int(rec["serving_target"]),
                                        int(rec["training_np"]))
            elif rec.get("op") == "snapshot":
                st = rec.get("state") or {}
                if st.get("fleet") is not None:
                    out[int(st.get("arbiter_seq", 0))] = (
                        int(st["fleet"]["serving_target"]),
                        int(st["fleet"]["training_np"]))
    return out


def test_coordinator_sigkill_mid_rebalance_replays_same_fleet(tmp_path):
    """Kill the coordinator mid-rebalance; journal replay must restore
    the exact confirmed fleet shape and the arbiter must continue the
    SAME sequence, not restart it."""
    key = _secret.make_secret_key()
    journal = str(tmp_path / "wal.jsonl")
    decisions = str(tmp_path / "decisions.jsonl")
    script = tmp_path / "arbiter_victim.py"
    script.write_text(ARBITER_VICTIM)
    env = _sub_env(tmp_path, KEY_HEX=key.hex(), JOURNAL=journal,
                   DECISIONS=decisions)
    proc = subprocess.Popen([sys.executable, str(script)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env)
    try:
        _wait_for(lambda: os.path.exists(decisions)
                  and len(open(decisions).read().splitlines()) >= 3,
                  timeout=120, what=">=3 confirmed decisions", proc=proc)
        os.kill(proc.pid, signal.SIGKILL)
        proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=30)

    confirmed = [json.loads(l) for l in open(decisions)
                 if l.strip()]
    assert len(confirmed) >= 3
    last = confirmed[-1]

    # (a) every confirmed decision is in the journal verbatim
    jarb = _journal_arbiter_records(journal)
    for d in confirmed:
        assert jarb[d["seq"]] == (d["serving_target"], d["training_np"]), \
            f"confirmed decision {d} lost or mangled in the journal"

    # (b) replay restores the last journaled shape; at most ONE decision
    # can be journaled-but-unconfirmed (killed between fsync and confirm)
    svc = CoordinatorService(key, bind_host="127.0.0.1",
                             journal_path=journal, restore=True)
    try:
        view = svc.fleet_view()
        assert view["fleet"] is not None
        assert view["arbiter_seq"] == max(jarb)
        assert last["seq"] <= view["arbiter_seq"] <= last["seq"] + 1
        if view["arbiter_seq"] == last["seq"]:
            assert view["fleet"]["serving_target"] == last["serving_target"]
            assert view["fleet"]["training_np"] == last["training_np"]
        assert (view["fleet"]["serving_target"]
                + view["fleet"]["training_np"]) == 8

        # (c) a new arbiter adopts the replayed shape and continues the
        # sequence: its next decision is seq+1, shifted by exactly one
        policy = ArbiterPolicy(queue_high=10.0, queue_low=1.0,
                               staleness_high_s=0, min_training_np=1,
                               min_replicas=1, max_replicas=6,
                               cooldown_s=0.0, sustain=1)
        arb = FleetArbiter(svc, total_hosts=8, policy=policy)
        assert arb.shape == {
            "serving_target": view["fleet"]["serving_target"],
            "training_np": view["fleet"]["training_np"]}
        grow = arb.shape["serving_target"] < policy.max_replicas
        q = 99.0 if grow else 0.0
        svc._record_metrics({"rank": 901,
                             "g": {"hvd_serving_queue_depth": q}})
        d = arb.evaluate(now=0.0)
        assert d is not None
        assert d["seq"] == view["arbiter_seq"] + 1
        step = 1 if grow else -1
        assert d["serving_target"] == view["fleet"]["serving_target"] + step
        assert d["serving_target"] + d["training_np"] == 8
    finally:
        svc.close()


# ------------------------------------------- replica faults mid-traffic

REPLICA_WORKER = """
import os
import time
import numpy as np
from horovod_tpu.checkpoint.store import BlobStore
from horovod_tpu.elastic.service import CoordinatorClient
from horovod_tpu.serving import InferenceServer, ModelRegistry
from horovod_tpu.serving.fleet import ReplicaAgent

key = bytes.fromhex(os.environ["KEY_HEX"])
store = BlobStore(os.path.join(os.environ["COMMIT_DIR"], "cas"))
reg = ModelRegistry(store=store)
assert reg.poll_store(store), "no published generation to adopt"


def forward(payload, inputs, padded_n):
    w = float(np.asarray(payload["attrs"]["w"]).reshape(-1)[0])
    return [w + float(q["x"]) for q in inputs]


srv = InferenceServer(reg, forward, window_s=0.002,
                      request_timeout_s=30.0,
                      rank=int(os.environ["REPLICA_RANK"]))
client = CoordinatorClient(os.environ["COORD_ADDR"], key,
                           watch_publish=True)
agent = ReplicaAgent(srv, client, replica_id=os.environ["REPLICA_ID"],
                     rank=int(os.environ["REPLICA_RANK"]))
assert agent.registered
agent.start()
if os.environ.get("ENABLE_PREEMPT_DRAIN") == "1":
    # Join the graceful-handoff plane: SIGTERM -> drain -> exit 0.
    assert agent.enable_preempt_drain(timeout_s=30.0)
print("ready", flush=True)
while not agent._closing:
    time.sleep(0.2)
print("drained", flush=True)
"""


def _published_commit_dir(tmp_path, w=7.0):
    d = str(tmp_path / "commits")
    os.makedirs(d, exist_ok=True)
    state = ObjectState(commit_dir=d, commit_async=False, w=np.float32(w))
    state.commit()
    pub = Publisher(d, every=1,
                    counters=lambda: {"steps_skipped": 0, "rollbacks": 0})
    assert pub.maybe_publish(state._commit_seq) is not None
    return d


def _spawn_fleet(tmp_path, service, key, commit_dir, n=3,
                 victim_idx=1, victim_fault=None, victim_env=None):
    script = tmp_path / "replica_worker.py"
    script.write_text(REPLICA_WORKER)
    procs = []
    for i in range(n):
        env = _sub_env(tmp_path, KEY_HEX=key.hex(),
                       COORD_ADDR=f"127.0.0.1:{service.port}",
                       COMMIT_DIR=commit_dir,
                       REPLICA_ID=f"chaos-{i}", REPLICA_RANK=901 + i)
        env[C.REPLICA_GRACE_ENV] = "60"
        if i == victim_idx and victim_fault:
            env["HOROVOD_FAULT_SPEC"] = victim_fault
        if i == victim_idx and victim_env:
            env.update(victim_env)
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env))
    return procs


def _registered_count(client):
    view = client.get_replicas()
    if view is None:
        return 0
    return len([r for r in view.get("replicas", [])
                if not r.get("draining")])


def _teardown(procs, service):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            pass
    service.close()


def test_replica_kill_mid_traffic_completes_all_requests(tmp_path,
                                                         monkeypatch):
    """The ISSUE acceptance: one of three replicas is SIGKILLed by the
    fault harness mid-traffic; all 100 accepted requests still complete
    via client failover — none hang, none surface a 5xx."""
    monkeypatch.setenv(C.REPLICA_GRACE_ENV, "60")
    key = _secret.make_secret_key()
    commit_dir = _published_commit_dir(tmp_path)
    service = CoordinatorService(key, bind_host="127.0.0.1",
                                 journal_path=str(tmp_path / "wal.jsonl"))
    procs = _spawn_fleet(tmp_path, service, key, commit_dir,
                         victim_fault="replica_kill:req=10")
    try:
        client = CoordinatorClient(f"127.0.0.1:{service.port}", key)
        _wait_for(lambda: _registered_count(client) == 3,
                  timeout=90, what="3 registered replicas")
        fc = FleetClient(coord=client, timeout_s=15.0, refresh_s=0.2,
                         max_tries=12)
        done = 0
        for i in range(100):
            out = fc.predict({"x": float(i)})
            assert out.get("ok"), out
            assert out["result"] == pytest.approx(7.0 + i)
            done += 1
        assert done == 100                      # 100/100, zero lost
        assert fc.stats["requests"] == 100
        # the victim really died (SIGKILL from the fault harness) and
        # the client really absorbed it
        victim = procs[1]
        _wait_for(lambda: victim.poll() is not None, timeout=30,
                  what="victim death")
        assert victim.returncode == -signal.SIGKILL
        assert fc.stats["failovers"] >= 1
        # the survivors are still alive and serving
        assert procs[0].poll() is None and procs[2].poll() is None
    finally:
        _teardown(procs, service)


def test_replica_hang_mid_traffic_times_out_and_fails_over(tmp_path,
                                                           monkeypatch):
    """A wedged replica (alive at the socket, never answers — the mode
    liveness probes miss) costs each hit one client timeout, never a
    lost request: all 20 requests complete via failover."""
    monkeypatch.setenv(C.REPLICA_GRACE_ENV, "60")
    key = _secret.make_secret_key()
    commit_dir = _published_commit_dir(tmp_path)
    service = CoordinatorService(key, bind_host="127.0.0.1",
                                 journal_path=str(tmp_path / "wal.jsonl"))
    procs = _spawn_fleet(tmp_path, service, key, commit_dir,
                         victim_fault="replica_hang:req=3")
    try:
        client = CoordinatorClient(f"127.0.0.1:{service.port}", key)
        _wait_for(lambda: _registered_count(client) == 3,
                  timeout=90, what="3 registered replicas")
        fc = FleetClient(coord=client, timeout_s=2.0, refresh_s=0.2,
                         max_tries=12)
        done = 0
        for i in range(20):
            out = fc.predict({"x": float(i)})
            assert out.get("ok"), out
            assert out["result"] == pytest.approx(7.0 + i)
            done += 1
        assert done == 20
        assert fc.stats["failovers"] >= 1       # the wedge was absorbed
        # wedged, not dead: the victim process is still running — the
        # failure mode only client-side timeouts catch
        assert procs[1].poll() is None
    finally:
        _teardown(procs, service)


def test_replica_sigterm_drains_gracefully_under_traffic(tmp_path,
                                                         monkeypatch):
    """The ISSUE 20 serving acceptance (np=2, real processes, real
    SIGTERM): the victim replica catches the reclaim signal through the
    lifecycle plane, drains — routing stops at the coordinator, in-flight
    requests finish — and exits 0. All 100 accepted requests complete;
    the FleetClient never sees a reset, only (at most) failover."""
    monkeypatch.setenv(C.REPLICA_GRACE_ENV, "60")
    key = _secret.make_secret_key()
    commit_dir = _published_commit_dir(tmp_path)
    service = CoordinatorService(key, bind_host="127.0.0.1",
                                 journal_path=str(tmp_path / "wal.jsonl"))
    procs = _spawn_fleet(tmp_path, service, key, commit_dir, n=2,
                         victim_idx=1,
                         victim_env={"ENABLE_PREEMPT_DRAIN": "1"})
    try:
        client = CoordinatorClient(f"127.0.0.1:{service.port}", key)
        _wait_for(lambda: _registered_count(client) == 2,
                  timeout=90, what="2 registered replicas")
        fc = FleetClient(coord=client, timeout_s=15.0, refresh_s=0.2,
                         max_tries=12)
        victim = procs[1]
        done = 0
        for i in range(100):
            if i == 20:
                victim.send_signal(signal.SIGTERM)   # the reclaim notice
            out = fc.predict({"x": float(i)})
            assert out.get("ok"), out
            assert out["result"] == pytest.approx(7.0 + i)
            done += 1
        assert done == 100                           # 100/100, zero lost
        assert fc.stats["requests"] == 100
        # graceful exit, not a kill: drain completed and the worker left
        # its loop with status 0
        _wait_for(lambda: victim.poll() is not None, timeout=30,
                  what="victim graceful exit")
        assert victim.returncode == 0
        # the victim deregistered itself (drain -> deregister-on-drained):
        # the registry converges to the lone survivor with no pruning
        _wait_for(lambda: _registered_count(client) == 1, timeout=30,
                  what="survivor-only registry")
        assert procs[0].poll() is None               # survivor serving
        assert fc.predict({"x": 1.0}).get("ok")
    finally:
        _teardown(procs, service)
