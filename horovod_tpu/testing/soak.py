"""Seeded chaos-soak harness: every fault kind against ONE live world.

Reference parity: upstream horovod proves each elastic failure mode with
its own scripted integration test (``test/integration/test_elastic_*``,
SURVEY.md §6) — one fault, one run, one assertion. This module is the
missing composition layer: a **seeded random schedule** drawn from the
full fault menu (``testing/faults.py`` — kill / hang / delay / corrupt /
nan / desync / torn / preempt / rpc_* / resume_* / replica_* /
traffic_spike) thrown at a single live np=3 train + publish + serve
world, with **global invariants** checked after the dust settles:

1.  the training job exits 0 and every surviving rank reaches the final
    step (no lost or phantom generations);
2.  the committed-step ledger covers every step exactly and is monotone
    across generations modulo bounded committed-rollback replay;
3.  zero accepted-request loss on the serving side — shedding under
    spike is allowed, a failed or hung accepted request is not;
4.  coordinator journal replay reproduces the final world (training
    driver journal) and the final fleet registry (serving journal);
5.  every abnormal exit left a post-mortem: flight dumps + incident
    reports when a crash-class fault fired, the "preempt flight ring
    dumped" trace when a preemption fired — and NO failure record when
    only graceful preemptions fired;
6.  the last published commit is resumable by a fresh process
    (``ObjectState.load_latest``);
7.  no orphaned processes survive the run (every child is tagged with a
    run id and /proc is swept afterwards);
8.  at least ``min_fired`` scheduled events actually fired (a soak that
    silently skipped its chaos is worse than one that failed), inside
    the wall-clock budget.

Determinism contract: :func:`make_schedule` is a pure function of its
seed — same seed, same schedule, byte for byte (pinned by
tests/test_soak.py). The *timeline* of a run still varies with
scheduling noise; the invariants are written against outcomes, not
timings, which is what makes the soak re-runnable as a guardrail
(benchmarks/soak.py → soak_history.jsonl).

Topology: the training arm is a REAL ``hvdrun`` subprocess over three
loopback hosts with per-host commit dirs (the blob-mesh resume seam the
``resume_*`` faults target becomes live whenever a preempted host
rejoins with stale blobs); the serving arm is real replica subprocesses
(InferenceServer + ReplicaAgent) joined to a harness-owned journaled
coordinator, fed by a publisher thread that gates the training arm's
newest commits into the serving plane — so one schedule genuinely
exercises train, publish, and serve at once.
"""

from __future__ import annotations

import glob
import json
import os
import random
import re
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..core.logging import get_logger

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Lethal step-axis faults: each retires a generation (or, for preempt,
#: gracefully shrinks one). The schedule spaces them so every generation
#: makes progress — a soak must terminate.
_LETHAL = ("preempt", "kill", "torn")

#: env: profile-independent run-id tag injected into every child process
#: so the post-run /proc sweep can find orphans (invariant 7).
RUN_ID_ENV = "SOAK_RUN_ID"

#: Profile knobs. "full" is the guardrail soak (benchmarks/soak.py);
#: "smoke" is the fixed-seed tier-1 variant (tests/test_soak.py) —
#: benign-heavy, lethal cap 1 (one preempt), sized to finish under a
#: minute on the 8-vCPU test mesh.
PROFILES: Dict[str, Dict[str, Any]] = {
    "full": dict(steps=110, events=26, step_sleep=0.15, replicas=3,
                 cooldown_s=15, min_np_env=2, time_budget_s=420.0,
                 min_fired=20, traffic_min=50, stall_s=30),
    "smoke": dict(steps=14, events=7, step_sleep=0.1, replicas=2,
                  cooldown_s=6, min_np_env=None, time_budget_s=75.0,
                  min_fired=5, traffic_min=10, stall_s=20),
}


# -- schedule generation ------------------------------------------------------


def make_schedule(seed: int, *, steps: int, events: int,
                  profile: str = "full") -> List[Dict[str, Any]]:
    """Draw a deterministic fault schedule from ``seed``.

    Pure: two calls with the same arguments return identical lists
    (pinned by tests/test_soak.py — the whole point of a seeded soak is
    that a red run is re-runnable). Events are dicts::

        {"kind": ..., "arm": "train"|"replica"|"traffic",
         "rank": int|None, "axis": "step"|"round"|"call"|"fetch"|"req",
         "at": int, "params": {...}}

    Termination constraints baked in: lethal step faults all target
    rank 1 (present in every np>=2 world, so they cannot be stranded by
    renumbering), are spaced so each generation commits fresh progress,
    and crash-class faults that feed blacklist strikes are capped below
    the ban threshold.
    """
    rng = random.Random(seed)
    out: List[Dict[str, Any]] = []
    used_steps: set = set()
    used_axis: Dict[str, set] = {"round": set(), "call": set()}

    def pick_axis(axis: str, lo: int, hi: int) -> int:
        # Distinct slots per axis: the fault hooks fire at most ONE
        # fault per counter tick, so two events sharing call=N would
        # shadow each other.
        for _ in range(64):
            s = rng.randrange(lo, max(lo + 1, hi))
            if s not in used_axis[axis]:
                break
        used_axis[axis].add(s)
        return s

    def ev(kind: str, arm: str, axis: str, at: int,
           rank: Optional[int] = None, **params: Any) -> None:
        out.append({"kind": kind, "arm": arm, "rank": rank,
                    "axis": axis, "at": int(at), "params": dict(params)})

    def pick_step(lo: int, hi: int) -> int:
        for _ in range(64):
            s = rng.randrange(lo, max(lo + 1, hi))
            if s not in used_steps:
                break
        used_steps.add(s)
        return s

    # Lethal plan first, on a spaced grid. full: two preemptions (the
    # tentpole path, once per cooldown cycle), one SIGKILL (the crash
    # path the preemptions must be distinguishable from), one torn
    # commit (exactly ONE: torn exits 1, which accrues a blacklist
    # strike — two on one host would ban it). smoke: one preemption.
    lethal = (["preempt", "kill", "preempt", "torn"]
              if profile == "full" else ["preempt"])
    lo, hi = 4, max(6, steps - 10)
    seg = max(8, (hi - lo) // max(1, len(lethal)))
    for i, kind in enumerate(lethal):
        at = min(hi - 1, lo + i * seg + rng.randrange(min(4, seg)))
        used_steps.update(range(at - 1, at + 2))
        ev(kind, "train", "step", at, rank=1)

    if profile == "full":
        # Serving-side chaos: one replica SIGKILLed mid-request, one
        # wedged (the failure liveness probes miss). Victim slots are
        # fixed (1 and 2) — the spec rides each victim's own env.
        ev("replica_kill", "replica", "req", rng.randrange(8, 26), slot=1)
        ev("replica_hang", "replica", "req", rng.randrange(20, 36), slot=2)
        # Opportunistic blob-mesh faults: they fire only when a rejoining
        # host actually delta-fetches (guaranteed plausible by the
        # preemptions above, not guaranteed to fire — min_fired absorbs).
        ev("resume_delay", "train", "fetch", 0,
           seconds=round(rng.uniform(0.5, 1.5), 2))
        ev("resume_corrupt", "train", "fetch", 1)

    # Offered-load spike(s): applied by the harness traffic thread.
    n_spikes = 2 if profile == "full" else 1
    for _ in range(n_spikes):
        ev("traffic_spike", "traffic", "req",
           rng.randrange(15, 46) if profile == "full"
           else rng.randrange(8, 21),
           factor=rng.choice([2, 3, 4]),
           seconds=round(rng.uniform(1.0, 2.0), 1))

    # Benign fill up to the requested event count, cycling the menu so
    # every kind appears before any repeats.
    benign = (["nan", "desync", "delay", "rpc_delay", "hang", "corrupt",
               "rpc_drop", "rpc_refuse", "rpc_garble", "rpc_badsig"]
              if profile == "full"
              else ["nan", "desync", "delay", "rpc_delay", "hang"])
    i = 0
    while len(out) < events:
        kind = benign[i % len(benign)]
        i += 1
        if kind in ("nan", "desync"):
            ev(kind, "train", "step", pick_step(2, steps - 2))
        elif kind == "hang":
            ev(kind, "train", "step", pick_step(2, steps - 2),
               seconds=round(rng.uniform(0.5, 1.5), 2))
        elif kind == "corrupt":
            # path= is a placeholder substituted at render time.
            ev(kind, "train", "step", pick_step(2, steps - 2),
               path="{state_dir}")
        elif kind == "delay":
            ev(kind, "train", "round", pick_axis("round", 2, 26),
               seconds=round(rng.uniform(0.2, 0.8), 2))
        else:   # rpc_*
            params = {}
            if kind == "rpc_delay":
                params["seconds"] = round(rng.uniform(0.3, 1.0), 2)
            # Low call indexes: a worker's coordinator client issues only
            # a dozen-odd calls per process lifetime (register + notify +
            # polls), so higher slots would never be reached.
            ev(kind, "train", "call", pick_axis("call", 3, 16), **params)
    return out


def schedule_to_specs(schedule: List[Dict[str, Any]], *, state_dir: str
                      ) -> Tuple[str, Dict[int, str], List[Dict[str, Any]]]:
    """Render a schedule into the ``HOROVOD_FAULT_SPEC`` grammar.

    Returns ``(train_spec, replica_specs, traffic_events)``: the train
    spec rides the hvdrun ``--fault-spec`` flag (all workers share it +
    one marker dir, so each event fires once per world), replica specs
    are keyed by victim slot (each victim subprocess carries only its
    own), and traffic events are applied by the harness traffic thread
    directly — offered load is a property of the driver, not of any
    replica (testing/faults.py docstring).
    """
    train_parts: List[str] = []
    replica_specs: Dict[int, List[str]] = {}
    traffic: List[Dict[str, Any]] = []
    for e in schedule:
        params = dict(e["params"])
        if e["arm"] == "traffic":
            traffic.append(e)
            continue
        kv = []
        if e["rank"] is not None:
            kv.append(f"rank={e['rank']}")
        kv.append(f"{e['axis']}={e['at']}")
        for k, v in sorted(params.items()):
            if k == "slot":
                continue
            if k == "path":
                v = str(v).format(state_dir=state_dir)
            kv.append(f"{k}={v}")
        part = f"{e['kind']}:{','.join(kv)}"
        if e["arm"] == "replica":
            replica_specs.setdefault(int(params["slot"]), []).append(part)
        else:
            train_parts.append(part)
    return (";".join(train_parts),
            {slot: ";".join(parts) for slot, parts in replica_specs.items()},
            traffic)


# -- child process templates --------------------------------------------------

#: The training worker: an elastic ObjectState loop with per-host commit
#: dirs (blob-mesh resume seam), every fault seam exercised per step
#: (on_step arms/fires step faults; maybe_poison/maybe_desync run the
#: nan/desync seams; allgather drives engine rounds for delay faults;
#: commits drive the torn seam), and a shared executed-step ledger
#: ("<step> <np>" appended by rank 0 just before the commit seam) the
#: coverage/monotonicity invariants read back.
SOAK_WORKER = """
import json
import os
import time
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.optimizer import allgather_object
from horovod_tpu.testing import faults

hvd.init()
N = int(os.environ["SOAK_STEPS"])
SLEEP = float(os.environ["SOAK_STEP_SLEEP"])
TRACE = os.environ["SOAK_TRACE_FILE"]
_dir = os.path.join(os.environ["SOAK_STATE_DIR"],
                    os.environ.get("HOROVOD_HOSTNAME", "local"))
state = elastic.ObjectState(commit_dir=_dir, step=0, w=np.float32(0.0))

@elastic.run
def train(state):
    while state.step < N:
        step = state.step
        allgather_object(float(step))
        faults.on_step(step, rank=hvd.rank())
        grads = faults.maybe_poison({"g": np.ones(4, np.float32)})
        params = faults.maybe_desync({"w": np.asarray(state.w)})
        time.sleep(SLEEP)
        state.w = np.float32(
            float(np.asarray(params["w"]).reshape(-1)[0]) + 1.0)
        state.step = step + 1
        # Ledger BEFORE commit: commit() is also the graceful-reset seam
        # (check_host_updates raises AFTER persisting), so a post-commit
        # write would lose the reset step forever. Pre-commit writes can
        # only DUPLICATE (crash before durability -> replay re-logs),
        # which the monotonicity invariant tolerates.
        if hvd.rank() == 0:
            with open(TRACE, "a") as f:
                f.write("%d %d\\n" % (step, hvd.size()))
        state.commit()
    return state.step

train(state)
print(json.dumps({"final_step": state.step, "size": hvd.size(),
                  "rank": hvd.rank()}), flush=True)
"""

#: A serving replica: InferenceServer + ReplicaAgent against the
#: harness coordinator, adopting published generations from the
#: training arm's commit store (tests/test_fleet_chaos.py is the
#: single-fault version of this worker).
SOAK_REPLICA = """
import os
import time
import numpy as np
from horovod_tpu.checkpoint.store import BlobStore
from horovod_tpu.elastic.service import CoordinatorClient
from horovod_tpu.serving import InferenceServer, ModelRegistry
from horovod_tpu.serving.fleet import ReplicaAgent

key = bytes.fromhex(os.environ["KEY_HEX"])
store = BlobStore(os.path.join(os.environ["SOAK_SERVE_DIR"], "cas"))
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
print("ready", flush=True)
while not agent._closing:
    time.sleep(0.2)
"""


# -- the soak run -------------------------------------------------------------


def _scan_orphans(run_id: str, retries: int = 8) -> List[int]:
    """Sweep /proc for live processes still tagged with our run id.
    Retries briefly: children observed mid-exit are not orphans."""
    needle = f"{RUN_ID_ENV}={run_id}".encode()
    me = os.getpid()
    found: List[int] = []
    for _ in range(retries):
        found = []
        for path in glob.glob("/proc/[0-9]*/environ"):
            try:
                with open(path, "rb") as fh:
                    data = fh.read()
            except OSError:
                continue
            if needle in data:
                pid = int(path.split("/")[2])
                if pid != me:
                    found.append(pid)
        if not found:
            return []
        time.sleep(0.5)
    return found


def _count_fired(marker_root: str) -> Dict[str, int]:
    """Fired events by kind, from the one-shot marker files every armed
    fault writes BEFORE acting (testing/faults.py) — the ground truth of
    "events survived", independent of log parsing."""
    by_kind: Dict[str, int] = {}
    for path in glob.glob(os.path.join(marker_root, "**", "hvd_fault.*"),
                          recursive=True):
        parts = os.path.basename(path).split(".")
        if len(parts) >= 3:
            by_kind[parts[2]] = by_kind.get(parts[2], 0) + 1
    return by_kind


def run_soak(seed: int, workdir: str, *, profile: str = "full",
             steps: Optional[int] = None, events: Optional[int] = None,
             time_budget_s: Optional[float] = None) -> Dict[str, Any]:
    """Run one seeded soak; returns the result record (``ok`` plus the
    per-invariant verdicts — never raises for an invariant failure, so
    the caller always gets the full picture)."""
    cfg = dict(PROFILES[profile])
    if steps is not None:
        cfg["steps"] = steps
    if events is not None:
        cfg["events"] = events
    if time_budget_s is not None:
        cfg["time_budget_s"] = time_budget_s
    steps = int(cfg["steps"])
    log = get_logger()
    t0 = time.monotonic()

    schedule = make_schedule(seed, steps=steps, events=int(cfg["events"]),
                             profile=profile)
    state_dir = os.path.join(workdir, "state")
    coord_dir = os.path.join(workdir, "coord")
    flight_dir = os.path.join(workdir, "flight")
    marker_root = os.path.join(workdir, "markers")
    serve_dir = os.path.join(workdir, "serve")
    for d in (state_dir, coord_dir, flight_dir, serve_dir,
              os.path.join(marker_root, "train")):
        os.makedirs(d, exist_ok=True)
    train_spec, replica_specs, traffic_events = schedule_to_specs(
        schedule, state_dir=state_dir)
    trace_path = os.path.join(workdir, "step_trace")
    run_id = f"hvdsoak-{seed}-{os.getpid()}"

    problems: List[str] = []
    invariants: Dict[str, bool] = {}

    def inv(name: str, cond: bool, detail: str = "") -> None:
        invariants[name] = bool(cond)
        if not cond:
            problems.append(f"{name}: {detail}" if detail else name)
            log.warning("soak invariant FAILED — %s (%s)", name, detail)

    # ---- serving plane: harness-owned journaled coordinator -------------
    from ..elastic import constants as C
    from ..elastic import journal as journal_mod
    from ..elastic.service import CoordinatorClient, CoordinatorService
    from ..elastic.state import ObjectState
    from ..checkpoint.store import newest_manifest_seq
    from ..runner import secret as _secret
    from ..serving import Publisher
    from ..serving.fleet import (FleetClient, FleetOverloadedError,
                                 FleetRequestError)

    key = _secret.make_secret_key()
    serve_journal = os.path.join(serve_dir, "wal.jsonl")
    svc = CoordinatorService(key, bind_host="127.0.0.1",
                             journal_path=serve_journal)
    admin = CoordinatorClient(f"127.0.0.1:{svc.port}", key)

    # ---- training arm: a real hvdrun over three loopback hosts ----------
    disco = os.path.join(workdir, "discover.sh")
    with open(disco, "w") as fh:
        fh.write("#!/bin/sh\necho localhost:1\necho 127.0.0.2:1\n"
                 "echo 127.0.0.3:1\n")
    os.chmod(disco, 0o755)
    worker_py = os.path.join(workdir, "soak_worker.py")
    with open(worker_py, "w") as fh:
        fh.write(SOAK_WORKER)

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        RUN_ID_ENV: run_id,
        "SOAK_STEPS": str(steps),
        "SOAK_STEP_SLEEP": str(cfg["step_sleep"]),
        "SOAK_TRACE_FILE": trace_path,
        "SOAK_STATE_DIR": state_dir,
        "HOROVOD_FAULT_MARKER_DIR": os.path.join(marker_root, "train"),
        "HOROVOD_FLIGHT_DIR": flight_dir,
        C.COORD_DIR_ENV: coord_dir,
        C.PREEMPT_COOLDOWN_ENV: str(cfg["cooldown_s"]),
        "HOROVOD_PEER_FAILURE_GRACE_SECONDS": "2",
        C.MIN_NP_WAIT_ENV: "90",
        "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": str(cfg["stall_s"]),
        "HOROVOD_LOG_LEVEL": "INFO",
    })
    if cfg["min_np_env"]:
        env[C.MIN_NP_ENV] = str(cfg["min_np_env"])
    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch",
           "-np", "3", "--min-np", "1", "--max-np", "3",
           "--host-discovery-script", disco,
           "--fault-spec", train_spec,
           sys.executable, worker_py]
    log.info("soak: launching training arm (seed=%d profile=%s %d events): "
             "%s", seed, profile, len(schedule), train_spec)
    train_proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True, env=env)

    stop = threading.Event()
    publishes = [0]
    traffic_stats = {"attempted": 0, "served": 0, "shed": 0, "failed": 0}
    spikes_applied = [0]
    replica_procs: List[subprocess.Popen] = []
    lh_dir = os.path.join(state_dir, "localhost")

    def _publish_loop() -> None:
        """Gate the training arm's newest commit into the serving plane
        whenever it advances (benchmarks/fleet.py publish cadence, but
        event-driven off the on-disk manifest seq — the harness has no
        in-process view of the workers' commit counters)."""
        pub = None
        last = -1
        while not stop.is_set():
            try:
                seq = newest_manifest_seq(lh_dir)
                if seq > last:
                    if pub is None:
                        pub = Publisher(
                            lh_dir, every=1,
                            counters=lambda: {"steps_skipped": 0,
                                              "rollbacks": 0})
                    rec = pub.maybe_publish(seq)
                    if rec is not None and admin.announce_publish(rec):
                        publishes[0] += 1
                        last = seq
            except Exception as err:    # noqa: BLE001 — chaos-tolerant:
                # a mid-write or fault-truncated manifest fails the
                # publish gate this tick and is retried on the next.
                log.info("soak publisher: skipped a tick (%s)", err)
            stop.wait(0.4)

    def _traffic_loop() -> None:
        """Serial request driver with schedule-applied load spikes; the
        zero-accepted-loss invariant reads these counters."""
        # timeout_s bounds what one wedged replica (replica_hang) costs
        # per round-robin hit before failover — it stays in the routing
        # set until the heartbeat grace deadline health-gates it, so a
        # long timeout here would throttle the whole driver.
        fc = FleetClient(coord=CoordinatorClient(
            f"127.0.0.1:{svc.port}", key), timeout_s=2.5, refresh_s=0.2,
            max_tries=12)
        spikes = sorted(traffic_events, key=lambda e: e["at"])
        spike_until = 0.0
        base_pause = 0.05
        while not stop.is_set():
            n = traffic_stats["attempted"]
            while spikes and n >= spikes[0]["at"]:
                e = spikes.pop(0)
                spike_until = time.monotonic() + float(
                    e["params"]["seconds"])
                spikes_applied[0] += 1
                log.warning("soak: traffic_spike at offered request %d "
                            "(factor=%s seconds=%s)", n,
                            e["params"]["factor"], e["params"]["seconds"])
            traffic_stats["attempted"] = n + 1
            try:
                out = fc.predict({"x": float(n)})
                if out.get("ok"):
                    traffic_stats["served"] += 1
                else:
                    traffic_stats["failed"] += 1
            except FleetOverloadedError:
                traffic_stats["shed"] += 1
            except FleetRequestError:
                traffic_stats["failed"] += 1
            if time.monotonic() >= spike_until:
                stop.wait(base_pause)

    pub_thread = threading.Thread(target=_publish_loop, daemon=True)
    pub_thread.start()

    # Replicas need a published generation to adopt; wait for the
    # training arm's first commit to clear the publish gate.
    deadline = time.monotonic() + 120
    while publishes[0] == 0 and time.monotonic() < deadline \
            and train_proc.poll() is None:
        time.sleep(0.2)
    serving_up = publishes[0] > 0
    traffic_thread: Optional[threading.Thread] = None
    if serving_up:
        replica_py = os.path.join(workdir, "soak_replica.py")
        with open(replica_py, "w") as fh:
            fh.write(SOAK_REPLICA)
        for i in range(int(cfg["replicas"])):
            renv = dict(env)
            renv.pop("HOROVOD_FAULT_SPEC", None)
            mdir = os.path.join(marker_root, f"replica{i}")
            os.makedirs(mdir, exist_ok=True)
            renv.update({
                "KEY_HEX": key.hex(),
                "COORD_ADDR": f"127.0.0.1:{svc.port}",
                "SOAK_SERVE_DIR": lh_dir,
                "REPLICA_ID": f"soak-{i}",
                "REPLICA_RANK": str(901 + i),
                "HOROVOD_FAULT_MARKER_DIR": mdir,
                C.REPLICA_GRACE_ENV: "5",
            })
            if i in replica_specs:
                renv["HOROVOD_FAULT_SPEC"] = replica_specs[i]
            replica_procs.append(subprocess.Popen(
                [sys.executable, replica_py], stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True, env=renv))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            view = admin.get_replicas()
            if view and len(view.get("replicas", [])) == len(replica_procs):
                break
            time.sleep(0.2)
        traffic_thread = threading.Thread(target=_traffic_loop, daemon=True)
        traffic_thread.start()

    # ---- ride out the chaos --------------------------------------------
    budget = float(cfg["time_budget_s"])
    timed_out = False
    try:
        t_out, t_err = train_proc.communicate(
            timeout=max(10.0, budget - (time.monotonic() - t0)))
    except subprocess.TimeoutExpired:
        timed_out = True
        train_proc.kill()
        t_out, t_err = train_proc.communicate(timeout=30)
    combined = t_out + t_err
    # Persist the training arm's log: a red invariant is diagnosed from
    # the workdir (callers that keep it), not from a vanished pipe.
    with open(os.path.join(workdir, "train.log"), "w") as fh:
        fh.write(combined)

    stop.set()
    if traffic_thread is not None:
        traffic_thread.join(timeout=30)
    pub_thread.join(timeout=10)
    # Serving journal parity is checked against the LIVE registry after
    # the publisher quiesces but before replica teardown (both sides
    # must have seen the same register/kill/drain mutations).
    serve_parity, serve_detail = True, ""
    if serving_up:
        jstate = journal_mod.replay(serve_journal)
        view = admin.get_replicas() or {}
        live_ids = sorted(r.get("replica_id", r.get("id"))
                          for r in view.get("replicas", []))
        jrep = sorted((jstate or {}).get("replicas", {}).keys())
        serve_parity = (jstate is not None and jrep == live_ids
                        and jstate.get("publish_seq") == publishes[0])
        serve_detail = (f"journal replicas {jrep} vs live {live_ids}; "
                        f"journal publish_seq "
                        f"{(jstate or {}).get('publish_seq')} vs "
                        f"announced {publishes[0]}")
    for p in replica_procs:
        if p.poll() is None:
            p.terminate()
    for p in replica_procs:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate(timeout=10)

    elapsed = time.monotonic() - t0
    fired_by_kind = _count_fired(marker_root)
    fired = sum(fired_by_kind.values()) + spikes_applied[0]

    # ---- invariants -----------------------------------------------------
    inv("train_completed", not timed_out and train_proc.returncode == 0,
        f"rc={train_proc.returncode} timed_out={timed_out} "
        f"tail={combined[-1500:]!r}")
    worker_lines = [json.loads(ln) for ln in t_out.splitlines()
                    if ln.startswith("{")]
    inv("final_step_reached",
        bool(worker_lines) and all(w["final_step"] == steps
                                   for w in worker_lines),
        f"worker exits: {worker_lines}")

    ledger: List[Tuple[int, int]] = []
    try:
        with open(trace_path) as fh:
            ledger = [tuple(map(int, ln.split())) for ln in fh
                      if ln.strip()]
    except OSError:
        pass
    led_steps = [s for s, _ in ledger]
    inv("step_coverage", sorted(set(led_steps)) == list(range(steps)),
        f"covered {len(set(led_steps))}/{steps} steps")
    # Monotone across generations modulo committed rollback: a crash may
    # legitimately replay the few steps between the last durable commit
    # and the death point; anything deeper means lost progress.
    deep = [(a, b) for a, b in zip(led_steps, led_steps[1:])
            if b <= a and b < a - 6]
    inv("step_monotone", not deep, f"rollbacks deeper than 6 steps: {deep}")

    if serving_up:
        inv("zero_request_loss",
            traffic_stats["failed"] == 0
            and traffic_stats["served"] >= int(cfg["traffic_min"]),
            f"traffic={traffic_stats} (min served {cfg['traffic_min']})")
        inv("journal_parity_serve", serve_parity, serve_detail)
    else:
        inv("serving_started", False, "no generation was ever published")

    # Training-driver journal replay must land on the final world: the
    # last launched generation exactly — or, benignly, a version AHEAD
    # of it (a cooled-down host rejoining in the race window between the
    # last step and driver exit journals a trailing update_world that
    # never launches). Replay landing BEHIND the last launch means lost
    # records.
    jtrain = journal_mod.replay(os.path.join(coord_dir,
                                             "coordinator.journal"))
    gens = [(int(m.group(1)), int(m.group(2))) for m in re.finditer(
        r"launching generation v(\d+) over .* \(np=(\d+)\)", combined)]
    inv("journal_parity_train",
        jtrain is not None and gens
        and ((jtrain["version"], jtrain["np"]) == gens[-1]
             or jtrain["version"] > gens[-1][0]),
        f"replayed (v={jtrain and jtrain['version']}, "
        f"np={jtrain and jtrain['np']}) vs last launch {gens[-1:]}")

    # Post-mortem completeness: crash-class faults must leave flight
    # evidence; graceful preemptions must leave their ring dump AND no
    # failure record (the whole point of the distinct preempt plane).
    crash_fired = (fired_by_kind.get("kill", 0)
                   + fired_by_kind.get("torn", 0))
    failure_seq = (jtrain or {}).get("failure_seq", -1)
    incidents = glob.glob(os.path.join(flight_dir, "incident_*.json"))
    if crash_fired:
        inv("flight_on_abnormal",
            failure_seq >= crash_fired and len(incidents) >= 1
            and bool(glob.glob(os.path.join(flight_dir, "flight_*.jsonl"))),
            f"failure_seq={failure_seq} incidents={len(incidents)} "
            f"for {crash_fired} crash fault(s)")
    else:
        inv("flight_on_abnormal",
            failure_seq == 0 and not incidents,
            f"failure record without a crash fault: seq={failure_seq} "
            f"incidents={incidents}")
    if fired_by_kind.get("preempt"):
        inv("preempt_graceful",
            "preempt flight ring dumped to" in combined
            and "no blacklist strike" in combined,
            "preempt fired without the graceful-handoff trace")

    # The last commit is resumable by a fresh process: the soak's
    # durable outcome. max over hosts — a host cooling down at exit
    # legitimately holds an older (but loadable) commit.
    best = -1
    for host_dir in sorted(glob.glob(os.path.join(state_dir, "*"))):
        try:
            st = ObjectState(commit_dir=host_dir, step=0)
            if st.load_latest():
                best = max(best, int(st.step))
        except Exception as err:    # noqa: BLE001 — a corrupt-fault
            log.info("soak: %s did not restore (%s)", host_dir, err)
    inv("commit_resumable", best == steps,
        f"freshest restorable commit at step {best}, want {steps}")

    orphans = _scan_orphans(run_id)
    inv("no_orphans", not orphans, f"pids still alive: {orphans}")

    inv("events_fired", fired >= int(cfg["min_fired"]),
        f"{fired} fired < {cfg['min_fired']} required "
        f"(by kind: {fired_by_kind})")
    inv("bounded", elapsed <= budget,
        f"{elapsed:.0f}s > {budget:.0f}s budget")

    svc.close()
    rec = {
        "bench": "soak", "seed": seed, "profile": profile, "steps": steps,
        "events_planned": len(schedule), "events_fired": fired,
        "fired_by_kind": fired_by_kind, "spikes_applied": spikes_applied[0],
        "generations": gens, "failure_seq": failure_seq,
        "publishes": publishes[0], "requests": dict(traffic_stats),
        "elapsed_s": round(elapsed, 1),
        "invariants": invariants, "problems": problems,
        "ok": all(invariants.values()),
    }
    log.info("soak: %s", json.dumps(rec, sort_keys=True))
    return rec
