"""End-to-end launcher integration: real ``hvdrun`` subprocesses on
localhost (the reference's test/integration/test_static_run.py pattern —
slots on 127.0.0.1 stand in for hosts; no ssh because the host is local)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import json
import os
import horovod_tpu as hvd
hvd.init()
print(json.dumps({
    "size": hvd.size(), "rank": hvd.rank(),
    "env_pid": os.environ.get("HOROVOD_PROCESS_ID"),
    "env_first_rank": os.environ.get("HOROVOD_FIRST_RANK"),
    "env_size": os.environ.get("HOROVOD_SIZE"),
}))
"""


def _run_hvdrun(args, timeout=240, env_extra=None):
    env = dict(os.environ, **(env_extra or {}))
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", *args],
        capture_output=True, text=True, timeout=timeout, env=env)


@pytest.mark.integration
def test_hvdrun_single_host_end_to_end(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    r = _run_hvdrun(["-np", "1", "-H", "localhost:1",
                     sys.executable, str(script)])
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads(r.stdout.strip().splitlines()[-1])
    assert payload["env_pid"] == "0" and payload["env_size"] == "1"
    assert payload["env_first_rank"] == "0"
    assert payload["size"] >= 1


@pytest.mark.integration
def test_hvdrun_propagates_worker_failure(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("raise SystemExit(3)\n")
    r = _run_hvdrun(["-np", "1", "-H", "localhost:1",
                     sys.executable, str(script)])
    assert r.returncode != 0


@pytest.mark.integration
def test_hvdrun_output_filename_redirects(tmp_path):
    script = tmp_path / "w.py"
    script.write_text("print('hello-from-rank')\n")
    out = tmp_path / "logs"
    r = _run_hvdrun(["-np", "1", "-H", "localhost:1",
                     "--output-filename", str(out),
                     sys.executable, str(script)])
    assert r.returncode == 0, r.stderr[-2000:]
    logs = list(out.rglob("*")) if out.exists() else []
    assert any("hello-from-rank" in f.read_text()
               for f in logs if f.is_file()), (logs, r.stdout)


MP_WORKER = """
import json
import jax
import numpy as np
import horovod_tpu as hvd

hvd.init()
assert jax.process_count() == 2

# 1. in-graph allreduce over the 2-process global mesh
from jax.sharding import PartitionSpec as P
from jax.experimental import multihost_utils
try:
    from jax import shard_map
    _kw = {"check_vma": False}
except ImportError:
    from jax.experimental.shard_map import shard_map
    _kw = {"check_rep": False}

f = jax.jit(shard_map(lambda x: hvd.allreduce(x), mesh=hvd.mesh(),
                      in_specs=P(hvd.RANK_AXIS), out_specs=P(), **_kw))
x = np.arange(hvd.size() * 2, dtype=np.float32).reshape(hvd.size(), 2)
gx = multihost_utils.host_local_array_to_global_array(
    x[hvd.rank():hvd.rank() + 1], hvd.mesh(), P(hvd.RANK_AXIS))
local = np.asarray(multihost_utils.global_array_to_host_local_array(
    f(gx), hvd.mesh(), P()))

# 2. JAX-path object collectives across REAL processes
from horovod_tpu.optimizer import allgather_object, broadcast_object
objs = allgather_object({"rank": hvd.rank()})
bobj = broadcast_object({"from": hvd.rank()} if hvd.rank() == 1 else None,
                        root_rank=1)

# 3. torch surface on the multi-process engine (JaxProcessEngine)
import torch
from horovod_tpu import torch as thvd
thvd.init()
t = thvd.allreduce(torch.tensor([float(thvd.rank() + 1)]), name="mp_ar")
g = thvd.allgather(torch.tensor([[thvd.rank()]]), name="mp_ag")
o = thvd.allgather_object(("r", thvd.rank()))
# device-backed payload path (engine._device_reduce): min-reduce and
# reducescatter over the process mesh
tmin = thvd.allreduce(torch.tensor([float(thvd.rank() + 1)]), name="mp_min",
                      op="min")
trs = thvd.reducescatter(torch.arange(4, dtype=torch.float32) * (thvd.rank() + 1),
                         name="mp_rs")
# process-set ops on the multi-host engine (member-mesh rounds): a
# singleton set while the OTHER rank does nothing — previously
# NotImplementedError on this engine
ps_solo = thvd.add_process_set([thvd.rank()])
tps = thvd.allreduce(torch.tensor([10.0 + thvd.rank()]), name=f"mp_ps{thvd.rank()}",
                     process_set=ps_solo)

print(json.dumps({
    "rank": hvd.rank(), "size": hvd.size(),
    "reduced": local.tolist(), "objs": objs, "bobj": bobj,
    "torch_ar": float(t), "torch_ag": g.flatten().tolist(),
    "torch_objs": o,
    "torch_min": float(tmin), "torch_rs": trs.flatten().tolist(),
    "torch_ps": float(tps),
}))
"""


@pytest.mark.integration
def test_hvdrun_two_process_collectives(tmp_path):
    """REAL 2-process jax.distributed job on localhost (gloo cross-process
    CPU collectives): in-graph allreduce, object collectives, and the
    torch JaxProcessEngine all in one launch — the reference's
    'horovodrun -np 2' CPU tier (SURVEY.md §4) as a live test."""
    script = tmp_path / "mp_worker.py"
    script.write_text(MP_WORKER)
    r = _run_hvdrun(["-np", "2", "-H", "localhost:1,127.0.0.1:1",
                     sys.executable, str(script)], timeout=360)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 2
    for out in lines:
        assert out["size"] == 2
        assert out["reduced"] == [[1.0, 2.0]]           # mean of rows
        assert out["objs"] == [{"rank": 0}, {"rank": 1}]
        assert out["bobj"] == {"from": 1}
        assert out["torch_ar"] == 1.5                   # mean of 1, 2
        assert out["torch_ag"] == [0, 1]
        assert [tuple(x) for x in out["torch_objs"]] == [("r", 0), ("r", 1)]
        assert out["torch_min"] == 1.0                  # min of 1, 2
        # sum of [0,1,2,3] and [0,2,4,6] = [0,3,6,9]; rank r keeps chunk r
        assert out["torch_rs"] == ([0.0, 3.0] if out["rank"] == 0
                                   else [6.0, 9.0])
        # singleton process set: each rank averaged only with itself
        assert out["torch_ps"] == 10.0 + out["rank"]


MP3_WORKER = """
import json
import torch
import horovod_tpu as hvd
from horovod_tpu import torch as thvd
hvd.init()
thvd.init()
r = thvd.rank()
if r in (0, 2):
    # proper multi-member subset: rounds ride a mesh that EXCLUDES rank 1,
    # which is concurrently free (it goes straight to the global op below)
    ps = thvd.add_process_set([0, 2])
    sub = float(thvd.allreduce(torch.tensor([float(r + 1)]), name="sub",
                               process_set=ps))
else:
    sub = -1.0
g = thvd.allgather(torch.tensor([[r]]), name="all")   # global op after
print(json.dumps({"rank": r, "sub": sub, "all": g.flatten().tolist()}))
"""


@pytest.mark.integration
def test_hvdrun_three_process_subgroup(tmp_path):
    """REAL 3-process run: a {0,2} process-set allreduce over the member
    mesh while rank 1 is outside it — the multi-host subgroup transport
    (engine._member_mesh) with genuinely partial process participation."""
    script = tmp_path / "mp3_worker.py"
    script.write_text(MP3_WORKER)
    r = _run_hvdrun(["-np", "3",
                     "-H", "localhost:1,127.0.0.1:1,127.0.0.2:1",
                     sys.executable, str(script)], timeout=360)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3
    for out in lines:
        assert out["all"] == [0, 1, 2]
        if out["rank"] in (0, 2):
            assert out["sub"] == 2.0        # mean of 1 and 3
        else:
            assert out["sub"] == -1.0


HIER_WORKER = """
import json
import os
os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
import jax
import numpy as np
import horovod_tpu as hvd
from jax.sharding import PartitionSpec as P
from jax.experimental import multihost_utils
try:
    from jax import shard_map
    _kw = {"check_vma": False}
except ImportError:
    from jax.experimental.shard_map import shard_map
    _kw = {"check_rep": False}

hvd.init()   # env var alone: auto cross x intra mesh
ctx = hvd.core.context()
assert isinstance(ctx.axis_name, tuple), ctx.axis_name
f = jax.jit(shard_map(lambda x: hvd.allreduce(x, hvd.Sum), mesh=ctx.mesh,
                      in_specs=P(ctx.axis_name), out_specs=P(), **_kw))
x = np.arange(hvd.size() * 2, dtype=np.float32).reshape(hvd.size(), 2)
gx = multihost_utils.host_local_array_to_global_array(
    x[hvd.rank():hvd.rank() + 1], ctx.mesh, P(ctx.axis_name))
local = np.asarray(multihost_utils.global_array_to_host_local_array(
    f(gx), ctx.mesh, P()))
# Subgroup op ON the zero-config hierarchical (tuple-axis) mesh —
# VERDICT r2 missing #1: setting the reference's own env var must not
# break process-set calls. Members {0, last} sum; others keep input.
ps = hvd.add_process_set([0, hvd.size() - 1])
g = jax.jit(shard_map(lambda x: hvd.allreduce(x, hvd.Sum, process_set=ps),
                      mesh=ctx.mesh, in_specs=P(ctx.axis_name),
                      out_specs=P(ctx.axis_name), **_kw))
sub = np.asarray(multihost_utils.global_array_to_host_local_array(
    g(gx), ctx.mesh, P(ctx.axis_name)))
print(json.dumps({"rank": hvd.rank(), "axes": list(ctx.axis_name),
                  "reduced": local.tolist(), "sub": sub.tolist()}))
"""


@pytest.mark.integration
def test_hvdrun_hierarchical_env_auto_mesh(tmp_path):
    """HOROVOD_HIERARCHICAL_ALLREDUCE=1 with NO other input: init() builds
    the cross x intra mesh from the process topology, the default
    allreduce reduces over it, and process-set ops compose with the
    tuple rank axis — the reference's zero-config contract."""
    script = tmp_path / "hier_worker.py"
    script.write_text(HIER_WORKER)
    r = _run_hvdrun(["-np", "3",
                     "-H", "localhost:1,127.0.0.1:1,127.0.0.2:1",
                     sys.executable, str(script)], timeout=360)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3
    for out in lines:
        assert out["axes"] == ["hvd_cross", "hvd_intra"]
        # rows [0,1]+[2,3]+[4,5]
        assert out["reduced"] == [[6.0, 9.0]]
        if out["rank"] in (0, 2):
            assert out["sub"] == [[4.0, 6.0]]   # rows 0 + 2
        else:
            assert out["sub"] == [[2.0, 3.0]]   # non-member keeps input


ELASTIC_WORKER = """
import os
import sys
marker = os.environ["ELASTIC_TEST_MARKER"]
if not os.path.exists(marker):
    with open(marker, "w") as f:
        f.write("gen0 failed")
    print("worker: failing first generation", flush=True)
    sys.exit(1)
print("worker: recovered-in-generation-2", flush=True)
"""


@pytest.mark.integration
def test_hvdrun_elastic_relaunches_failed_generation(tmp_path):
    """REAL elastic launch: --host-discovery-script drives the
    ElasticDriver; the worker crashes in generation 0, the driver retires
    the generation and relaunches, generation 1 succeeds — the reference's
    elastic recovery loop (SURVEY §3.4) end-to-end with live processes."""
    disco = tmp_path / "discover.sh"
    disco.write_text("#!/bin/sh\necho localhost:1\n")
    disco.chmod(0o755)
    worker = tmp_path / "elastic_worker.py"
    worker.write_text(ELASTIC_WORKER)
    marker = tmp_path / "marker"
    r = _run_hvdrun(["-np", "1", "--min-np", "1", "--max-np", "1",
                     "--host-discovery-script", str(disco),
                     sys.executable, str(worker)],
                    env_extra={"ELASTIC_TEST_MARKER": str(marker)})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert marker.exists()
    combined = r.stdout + r.stderr
    assert "failing first generation" in combined
    assert "recovered-in-generation-2" in combined


WATCHDOG_WORKER = """
import os
import time
import horovod_tpu as hvd
hvd.init()
from horovod_tpu.core.exceptions import HorovodInternalError
from horovod_tpu.optimizer import allgather_object

if hvd.rank() == 0:
    # plays the dead peer: never joins rank 1's round (outlives rank 1's
    # watchdog window), then exits without the atexit distributed-shutdown
    # barrier (its peer is long gone)
    time.sleep(25)
    os._exit(0)

t0 = time.monotonic()
try:
    allgather_object(("probe", hvd.rank()))
    print("UNEXPECTED-COMPLETION", flush=True)
    os._exit(1)
except HorovodInternalError:
    print("WATCHDOG-UNBLOCKED %.1f" % (time.monotonic() - t0), flush=True)

# the engine is transport-lost now: the next op must fail fast, not hang
t1 = time.monotonic()
try:
    allgather_object("again")
    os._exit(1)
except HorovodInternalError:
    print("TRANSPORT-LOST-FAST %.2f" % (time.monotonic() - t1), flush=True)
os._exit(0)
"""


@pytest.mark.integration
def test_watchdog_unblocks_survivor_of_silent_peer(tmp_path):
    """VERDICT r4 #1 (mechanism): a rank blocked in an engine round against
    a peer that never participates UNBLOCKS ITSELF with
    HorovodInternalError after HOROVOD_STALL_SHUTDOWN_TIME_SECONDS — the
    reference's collective-error signal (NCCL abort / Gloo timeout)
    recreated at the JaxProcessEngine transport boundary. No driver
    involvement: this is the in-worker failure signal itself."""
    script = tmp_path / "watchdog_worker.py"
    script.write_text(WATCHDOG_WORKER)
    r = _run_hvdrun(["-np", "2", "-H", "localhost:1,127.0.0.2:1",
                     sys.executable, str(script)], timeout=240,
                    env_extra={"HOROVOD_STALL_CHECK_TIME_SECONDS": "2",
                               "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "6"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    out = r.stdout
    assert "UNEXPECTED-COMPLETION" not in out
    unblocked = [l for l in out.splitlines()
                 if l.startswith("WATCHDOG-UNBLOCKED")]
    assert unblocked, out
    # bounded: the 6s window, not the 25s peer sleep (slack for slow CI)
    assert 5.0 <= float(unblocked[0].split()[1]) <= 20.0, unblocked
    fast = [l for l in out.splitlines() if l.startswith("TRANSPORT-LOST-FAST")]
    assert fast and float(fast[0].split()[1]) < 1.0, out


CHAOS_WORKER = """
import json
import os
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.optimizer import allgather_object
from horovod_tpu.testing import faults

hvd.init()
state = elastic.ObjectState(step=0, total=0.0)

@elastic.run
def train(state):
    while state.step < 8:
        vals = allgather_object(float(state.step))
        if faults.will_fire("kill", state.step, rank=hvd.rank()):
            # Stage the membership change the kill implies BEFORE dying,
            # exactly like a real host loss: discovery stops reporting it.
            with open(os.environ["CHAOS_HOSTS_FILE"], "w") as f:
                f.write("localhost:1\\n")
        faults.on_step(state.step, rank=hvd.rank())   # dies MID-step
        state.total += float(sum(vals))
        state.step += 1
        state.commit()
    return state.step

train(state)
print(json.dumps({"final_step": state.step, "size": hvd.size(),
                  "total": state.total}), flush=True)
"""


@pytest.mark.integration
def test_elastic_sigkill_mid_collective_shrinks_and_resumes(tmp_path):
    """VERDICT r4 #1 (end to end): 2 real workers in a steady engine-
    collective loop; rank 1 is SIGKILLed mid-step (after removing its host
    from discovery). The survivor — blocked in the next round — is
    unblocked bounded (driver fate-sharing kill, or its own watchdog),
    the generation retires, the driver relaunches at np=1, and
    ObjectState.load_latest resumes from the last commit: the final total
    is only reachable by 4 committed 2-rank steps + 4 resumed 1-rank
    steps (fresh np=1: 28, full np=2: 56)."""
    hosts_file = tmp_path / "chaos_hosts"
    hosts_file.write_text("localhost:1\n127.0.0.2:1\n")
    disco = tmp_path / "discover.sh"
    disco.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    disco.chmod(0o755)
    script = tmp_path / "chaos_worker.py"
    script.write_text(CHAOS_WORKER)
    r = _run_hvdrun(["-np", "2", "--min-np", "1", "--max-np", "2",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "kill:rank=1,step=3",
                     sys.executable, str(script)], timeout=300,
                    env_extra={"CHAOS_HOSTS_FILE": str(hosts_file),
                               "HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "8",
                               "HOROVOD_LOG_LEVEL": "INFO"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert lines, r.stdout
    final = lines[-1]
    # generation 0: steps 0-3 at np=2 (total 0+2+4+6=12, committed), then
    # generation 1 resumes at step 4 with np=1: 12+4+5+6+7 = 34
    assert final == {"final_step": 8, "size": 1, "total": 34.0}, final
    combined = r.stdout + r.stderr
    assert "(np=2)" in combined      # generation 0 launched at 2
    assert "(np=1)" in combined      # retired and relaunched shrunk


JIT_CHAOS_WORKER = """
import json
import os
import jax
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.core.watchdog import monitored_step
from horovod_tpu.testing import faults
from jax.sharding import PartitionSpec as P
from jax.experimental import multihost_utils
try:
    from jax import shard_map
    _kw = {"check_vma": False}
except ImportError:
    from jax.experimental.shard_map import shard_map
    _kw = {"check_rep": False}

hvd.init()
mesh = hvd.mesh()
f = jax.jit(shard_map(lambda x: hvd.allreduce(x, hvd.Sum), mesh=mesh,
                      in_specs=P(hvd.RANK_AXIS), out_specs=P(), **_kw))

def psum_step(v):
    # The IN-GRAPH data plane: each rank contributes v, the jitted
    # collective sums across the process mesh. Against a dead/hung peer
    # this blocks INSIDE the XLA runtime — no Python frame, no signal
    # handler, nothing the engine stall watchdog can see.
    x = np.full((hvd.size(), 1), v, np.float32)
    gx = multihost_utils.host_local_array_to_global_array(
        x[hvd.rank():hvd.rank() + 1], mesh, P(hvd.RANK_AXIS))
    return float(np.asarray(multihost_utils.global_array_to_host_local_array(
        f(gx), mesh, P()))[0])

mstep = monitored_step(psum_step, what="chaos_jit_step")
state = elastic.ObjectState(step=0, total=0.0)

@elastic.run
def train(state):
    # Compile OUTSIDE any deadline: a legitimate first step includes XLA
    # compilation, which must never count against the step timeout.
    psum_step(0.0)
    while state.step < 6:
        if faults.will_fire("kill", state.step, rank=hvd.rank()):
            # A killed host also vanishes from discovery, like real life.
            hosts_file = os.environ.get("CHAOS_HOSTS_FILE")
            if hosts_file:
                with open(hosts_file, "w") as fh:
                    fh.write("localhost:1\\n")
        faults.on_step(state.step, rank=hvd.rank())
        state.total += mstep(float(state.step))
        state.step += 1
        state.commit()
    return state.step

train(state)
print(json.dumps({"final_step": state.step, "size": hvd.size(),
                  "total": state.total}), flush=True)
"""


@pytest.mark.integration
def test_fate_sharing_rescues_jit_blocked_survivor(tmp_path):
    """The STALL=0 rescue (docs/failure_model.md): 2 real workers in a
    JITTED shard_map collective loop with the engine stall watchdog
    explicitly DISABLED. Rank 1 is SIGKILLed by the fault harness at step
    3; rank 0 is blocked inside the compiled collective where no Python
    exception can reach it. The driver learns of the death first
    (fate-sharing), publishes it on /world (peer-liveness push) and
    SIGTERM→SIGKILLs the wedged survivor; whichever rescue lands first
    retires the generation, and the relaunched np=1 world resumes from the
    last commit — within a bounded, asserted wall time."""
    import time
    hosts_file = tmp_path / "jit_chaos_hosts"
    hosts_file.write_text("localhost:1\n127.0.0.2:1\n")
    disco = tmp_path / "discover.sh"
    disco.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    disco.chmod(0o755)
    script = tmp_path / "jit_chaos_worker.py"
    script.write_text(JIT_CHAOS_WORKER)
    t0 = time.monotonic()
    r = _run_hvdrun(["-np", "2", "--min-np", "1", "--max-np", "2",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "kill:rank=1,step=3",
                     sys.executable, str(script)], timeout=300,
                    env_extra={"CHAOS_HOSTS_FILE": str(hosts_file),
                               "HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "0",
                               "HOROVOD_LOG_LEVEL": "INFO"})
    elapsed = time.monotonic() - t0
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    combined = r.stdout + r.stderr
    # STALL=0 really was in force (driver logs the armed window per
    # generation) — the r5 engine watchdog could NOT have done this rescue.
    assert "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS=0" in combined, combined
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert lines, r.stdout
    # gen 0 commits steps 0-2 at np=2 (total 0+2+4=6); gen 1 resumes at
    # step 3 with np=1: 6+3+4+5 = 18. Only reachable via load_latest.
    assert lines[-1] == {"final_step": 6, "size": 1, "total": 18.0}, lines
    assert "(np=2)" in combined and "(np=1)" in combined
    # Bounded: one rescue (seconds) + two generations of tiny steps. The
    # spec's own number: far under the 300s harness timeout, and far under
    # the 600s default stall window the test turned off.
    assert elapsed < 240, f"rescue not bounded: {elapsed:.0f}s"


@pytest.mark.integration
def test_step_monitor_rescues_hung_jit_peer(tmp_path):
    """The jit-step deadline monitor end to end: rank 1 HANGS (fault
    harness ``hang`` — alive but never participating, so the driver's
    fate-sharing sees nothing and there is no death to publish) while rank
    0 blocks inside the jitted collective. With STALL=0 the only rescue is
    ``HOROVOD_STEP_TIMEOUT_SECONDS``: rank 0's monitor abandons the step,
    exits RESTART, the driver tears down the hung peer and relaunches at
    np=2, and the job resumes from the last commit."""
    import time
    disco = tmp_path / "discover.sh"
    disco.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.2:1\n")
    disco.chmod(0o755)
    script = tmp_path / "hang_chaos_worker.py"
    script.write_text(JIT_CHAOS_WORKER)
    t0 = time.monotonic()
    r = _run_hvdrun(["-np", "2", "--min-np", "2", "--max-np", "2",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "hang:rank=1,step=3",
                     "--step-timeout-seconds", "8",
                     sys.executable, str(script)], timeout=300,
                    env_extra={"HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "0",
                               "HOROVOD_LOG_LEVEL": "INFO"})
    elapsed = time.monotonic() - t0
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    combined = r.stdout + r.stderr
    assert "monitored step abandoned" in combined, combined
    assert "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS=0" in combined, combined
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    # Both ranks of the FINAL generation reach the print. gen 0 commits
    # steps 0-2 at np=2 (0+2+4=6); gen 1 replays nothing (fault marker is
    # one-shot) and finishes steps 3-5 at np=2: 6+6+8+10 = 30.
    assert len(lines) == 2, (lines, r.stdout)
    for out in lines:
        assert out == {"final_step": 6, "size": 2, "total": 30.0}, lines
    # two generations, both at np=2
    assert combined.count("(np=2)") >= 2, combined
    assert elapsed < 240, f"rescue not bounded: {elapsed:.0f}s"


GROW_WORKER = """
import json
import os
import time
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.optimizer import allgather_object

hvd.init()
state = elastic.ObjectState(step=0)

@elastic.run
def train(state):
    while state.step < 12:
        allgather_object(float(state.step))
        if (hvd.rank() == 0 and state.step == 2
                and not os.path.exists(os.environ["GROW_MARKER"])):
            with open(os.environ["GROW_MARKER"], "w") as f:
                f.write("grown")
            with open(os.environ["GROW_HOSTS_FILE"], "w") as f:
                f.write("localhost:1\\n127.0.0.2:1\\n127.0.0.3:1\\n")
        time.sleep(0.3)
        state.step += 1
        state.commit()
    return state.step

train(state)
from horovod_tpu.elastic import constants as C
_cas = os.path.join(os.environ[C.COMMIT_DIR_ENV], "cas")
print(json.dumps({"rank": hvd.rank(), "size": hvd.size(),
                  "final_step": state.step,
                  "manifests": sorted(
                      f for f in os.listdir(_cas)
                      if f.startswith("manifest.")) if os.path.isdir(_cas)
                  else [],
                  "resume_latency_s": getattr(
                      state, "_last_resume_latency_s", None)}), flush=True)
"""


@pytest.mark.integration
def test_elastic_host_add_graceful_reset_two_workers(tmp_path):
    """VERDICT r4 weak #4: >=2 REAL workers running when capacity arrives.
    Discovery gains a third host mid-generation; the driver bumps the
    world version (graceful — no kill), both workers take
    HostsUpdatedInterrupt at their next commit and exit RESTART, and the
    job finishes at np=3 resumed from the last commit."""
    hosts_file = tmp_path / "grow_hosts"
    hosts_file.write_text("localhost:1\n127.0.0.2:1\n")
    disco = tmp_path / "discover.sh"
    disco.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    disco.chmod(0o755)
    script = tmp_path / "grow_worker.py"
    script.write_text(GROW_WORKER)
    r = _run_hvdrun(["-np", "2", "--min-np", "2", "--max-np", "3",
                     "--host-discovery-script", str(disco),
                     sys.executable, str(script)], timeout=300,
                    env_extra={"GROW_MARKER": str(tmp_path / "grown"),
                               "GROW_HOSTS_FILE": str(hosts_file),
                               "HOROVOD_LOG_LEVEL": "INFO"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    # only the final generation's workers reach the print — all 3 of them
    assert len(lines) == 3, (lines, r.stdout)
    assert all(l["size"] == 3 and l["final_step"] == 12 for l in lines), lines
    combined = r.stdout + r.stderr
    assert "hosts gained" in combined
    assert "(np=3)" in combined
    # the regrown generation resumed from the content-addressed store —
    # every worker (including the brand-new third rank, which fetched the
    # blobs it lacked) saw published manifests and a SUB-SECOND restore
    for l in lines:
        assert l["manifests"], l
        assert l["resume_latency_s"] is not None, l
        assert l["resume_latency_s"] < 1.0, l
    import re
    lat = [float(m) for m in
           re.findall(r"resume latency ([0-9.]+)s", combined)]
    assert lat and max(lat) < 1.0, (lat, combined[-2000:])


RESUME_MESH_WORKER = """
import json
import os
import time
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.elastic.state import last_resume_stats
from horovod_tpu.optimizer import allgather_object

hvd.init()
# Per-HOST commit dirs: each loopback "host" owns a private disk, so the
# freshly-grown third host holds NO blobs and must restore over the peer
# blob mesh (elastic/blobmesh.py) — the seam the resume_* faults target.
_dir = os.path.join(os.environ["MESH_DIR"],
                    os.environ.get("HOROVOD_HOSTNAME", "local"))
state = elastic.ObjectState(commit_dir=_dir, step=0)

@elastic.run
def train(state):
    while state.step < 8:
        allgather_object(float(state.step))
        if (hvd.rank() == 0 and state.step == 2
                and not os.path.exists(os.environ["GROW_MARKER"])):
            with open(os.environ["GROW_MARKER"], "w") as f:
                f.write("grown")
            with open(os.environ["GROW_HOSTS_FILE"], "w") as f:
                f.write("localhost:1\\n127.0.0.2:1\\n127.0.0.3:1\\n")
        time.sleep(0.2)
        state.step += 1
        state.commit()
    return state.step

train(state)
stats = last_resume_stats()
print(json.dumps({"rank": hvd.rank(), "size": hvd.size(),
                  "final_step": state.step,
                  "host": os.environ.get("HOROVOD_HOSTNAME"),
                  "resume_latency_s": getattr(
                      state, "_last_resume_latency_s", None),
                  "bytes_fetched": stats.get("bytes_fetched"),
                  "retries": stats.get("retries"),
                  "topology_from": stats.get("topology_from")}), flush=True)
"""


def _run_resume_mesh_chaos(tmp_path, fault_spec, extra_env=None):
    hosts_file = tmp_path / "mesh_hosts"
    hosts_file.write_text("localhost:1\n127.0.0.2:1\n")
    disco = tmp_path / "discover.sh"
    disco.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    disco.chmod(0o755)
    script = tmp_path / "mesh_worker.py"
    script.write_text(RESUME_MESH_WORKER)
    env = {"MESH_DIR": str(tmp_path / "mesh"),
           "GROW_MARKER": str(tmp_path / "grown"),
           "GROW_HOSTS_FILE": str(hosts_file),
           "HOROVOD_FAULT_MARKER_DIR": str(tmp_path / "fault_markers"),
           "HOROVOD_LOG_LEVEL": "INFO"}
    env.update(extra_env or {})
    return _run_hvdrun(["-np", "2", "--min-np", "2", "--max-np", "3",
                        "--host-discovery-script", str(disco),
                        "--fault-spec", fault_spec,
                        sys.executable, str(script)], timeout=420,
                       env_extra=env)


@pytest.mark.integration
@pytest.mark.slow
def test_resume_mesh_corrupt_source_reelects_np3(tmp_path):
    """ISSUE 18 chaos tier: the world grows 2→3 hosts with per-host
    disks; the new host's first peer-fetched blob is garbled IN FLIGHT
    (``resume_corrupt`` — HMAC-valid, so only the content-address re-hash
    catches it). The fetcher re-elects the surviving possessor, the
    restored state is digest-verified, and training completes at np=3
    with NO extra generation. Per-rank byte accounting: only the blobless
    new host fetched; the old hosts' need sets were empty (the PR 9
    union-broadcast over-delivery is gone)."""
    r = _run_resume_mesh_chaos(tmp_path, "resume_corrupt:fetch=0")
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3, (lines, r.stdout)
    assert all(l["size"] == 3 and l["final_step"] == 8 for l in lines), lines
    combined = r.stdout + r.stderr
    assert "re-electing next possessor" in combined, combined[-3000:]
    by_host = {l["host"]: l for l in lines}
    fresh = by_host["127.0.0.3"]
    # the corrupt reply cost at least one re-election, then verified bytes
    assert fresh["retries"] >= 1, fresh
    assert fresh["bytes_fetched"] > 0, fresh
    # old hosts possess every blob — their own need sets fetched nothing
    for host in ("localhost", "127.0.0.2"):
        assert by_host[host]["bytes_fetched"] == 0, by_host[host]
        assert by_host[host]["retries"] == 0, by_host[host]
    # topology-change restore: the adopted manifest came from the np=2 world
    assert fresh["topology_from"] == 2, fresh
    # happy-path latency bound survives the failover (loopback fetches)
    for l in lines:
        assert l["resume_latency_s"] is not None, l
        assert l["resume_latency_s"] < 5.0, l


@pytest.mark.integration
@pytest.mark.slow
def test_resume_mesh_source_sigkill_mid_fetch_np3(tmp_path):
    """ISSUE 18 chaos tier: SIGKILL the ELECTED blob source while it
    serves the new host's first fetch (``resume_kill``). The fetcher
    re-elects the surviving possessor and finishes its fetch; the dead
    peer bounds the resume barrier out (stall watchdog, under the resume
    deadline ceiling), the driver relaunches, and the one-shot marker
    lets the next generation resume clean — training still completes at
    np=3."""
    r = _run_resume_mesh_chaos(
        tmp_path, "resume_kill:fetch=0",
        extra_env={"HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "8"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3, (lines, r.stdout)
    assert all(l["size"] == 3 and l["final_step"] == 8 for l in lines), lines
    combined = r.stdout + r.stderr
    assert "fault: killing self while serving blob" in combined, \
        combined[-3000:]
    assert "re-electing next possessor" in combined, combined[-3000:]
    # the kill retired a generation: np=3 was launched at least twice
    assert combined.count("(np=3)") >= 2, combined[-3000:]
    # The final generation's resume went through the mesh path too. The
    # new host may fetch ZERO bytes this time — everything it pulled
    # before the barrier stalled persisted in its store, which is the
    # point of landing verified bytes immediately — so assert the
    # topology-change restore, not a byte count.
    by_host = {l["host"]: l for l in lines}
    assert by_host["127.0.0.3"]["topology_from"] == 2, by_host
    assert by_host["127.0.0.3"]["resume_latency_s"] is not None, by_host


@pytest.mark.integration
def test_hvdrun_timeline_flag_reaches_worker(tmp_path):
    """--timeline-filename → HOROVOD_TIMELINE in the worker env → init
    writes a chrome trace (reference: horovodrun --timeline-filename)."""
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    trace = tmp_path / "t.json"
    r = _run_hvdrun(["-np", "1", "-H", "localhost:1",
                     "--timeline-filename", str(trace),
                     sys.executable, str(script)])
    assert r.returncode == 0, r.stderr[-2000:]
    assert trace.exists()
    text = trace.read_text()
    assert '"traceEvents"' in text or text.strip().startswith("[")


TF_WORKER = """
import json
import numpy as np
import horovod_tpu as hvdj
hvdj.init()   # brings up jax.distributed from the launcher's env
import tensorflow as tf
import horovod_tpu.tensorflow as hvd

hvd.init()
assert hvd.size() == 2

@tf.function
def step(x):
    return hvd.allreduce(x, op=hvd.Sum, name="graph_ar") * 2.0

out = step(tf.constant([float(hvd.rank() + 1)])).numpy()

v = tf.Variable(np.full((2,), float(hvd.rank()), np.float32))
hvd.broadcast_variables([v], root_rank=1)

with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
    w = tf.Variable([2.0])
    loss = tf.reduce_sum(w * (hvd.rank() + 1.0))
g = tape.gradient(loss, [w])[0]

# Keras model.fit ACROSS the two processes: compiled train_step traces
# apply_gradients -> fused bucket allreduce through the py_function
# boundary on the production engine; ranks must converge identically.
import keras
from horovod_tpu.tensorflow.keras import BroadcastGlobalVariablesCallback
m = keras.Sequential([keras.layers.Dense(1, use_bias=False)])
m.build((None, 2))
m.set_weights([np.full((2, 1), float(hvd.rank() + 1), np.float32)])
opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.05))
m.compile(optimizer=opt, loss="mse")
rngk = np.random.RandomState(hvd.rank())
xk = rngk.randn(64, 2).astype(np.float32)
yk = (xk @ np.array([1.0, -1.0], np.float32)).astype(np.float32)
hist = m.fit(xk, yk, batch_size=32, epochs=3, verbose=0,
             callbacks=[BroadcastGlobalVariablesCallback(0)])
fit_w = m.get_weights()[0].ravel().tolist()

# backward_passes_per_step=2 in the SAME compiled model.fit path (r4:
# graph-mode aggregation — accumulators + traced tf.cond): trains
# correctly and ranks converge identically.
m2 = keras.Sequential([keras.layers.Dense(1, use_bias=False)])
m2.build((None, 2))
m2.set_weights([np.full((2, 1), float(hvd.rank() + 1), np.float32)])
opt2 = hvd.DistributedOptimizer(keras.optimizers.SGD(0.05),
                                backward_passes_per_step=2)
m2.compile(optimizer=opt2, loss="mse")
hist2 = m2.fit(xk, yk, batch_size=16, epochs=4, verbose=0,
               callbacks=[BroadcastGlobalVariablesCallback(0)])
bpps_w = m2.get_weights()[0].ravel().tolist()

print(json.dumps({"rank": hvd.rank(), "graph": out.tolist(),
                  "bcast": np.asarray(v).tolist(),
                  "grad": np.asarray(g).tolist(),
                  "fit_w": fit_w, "fit_improved":
                  hist.history["loss"][-1] < hist.history["loss"][0],
                  "bpps_w": bpps_w, "bpps_improved":
                  hist2.history["loss"][-1] < hist2.history["loss"][0]}))
"""


@pytest.mark.integration
def test_hvdrun_tensorflow_binding(tmp_path):
    """The TF binding over the production JaxProcessEngine with 2 real
    processes: tf.function allreduce (py_function boundary),
    broadcast_variables, DistributedGradientTape averaging."""
    script = tmp_path / "tf_worker.py"
    script.write_text(TF_WORKER)
    r = _run_hvdrun(["-np", "2", "-H", "localhost:1,127.0.0.1:1",
                     sys.executable, str(script)], timeout=360)
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 2
    for out in lines:
        assert out["graph"] == [6.0]        # (1+2)*2
        assert out["bcast"] == [1.0, 1.0]   # root 1's value
        assert out["grad"] == [1.5]         # mean of 1 and 2
        assert out["fit_improved"], out     # compiled fit trains
        assert out["bpps_improved"], out    # graph-mode bpps=2 trains
    # both ranks converge to IDENTICAL weights (broadcast + allreduce)
    assert lines[0]["fit_w"] == lines[1]["fit_w"], lines
    assert lines[0]["bpps_w"] == lines[1]["bpps_w"], lines


# --- control-plane chaos tier (ISSUE 4) --------------------------------------
# Multi-process coordinator crash-restart / flaky-control-plane scenarios.
# Marked slow: each one runs multiple real worker generations; tier-1
# (-m 'not slow') keeps its timeout budget without them.

COORD_CHAOS_WORKER = """
import json
import os
import signal
import time
# The survivor must be rescued by the PEER-LIVENESS PUSH, nothing else:
# ignore SIGTERM (a rank wedged inside the compiled runtime cannot run a
# Python signal handler either), leaving only the push and the driver's
# 5s SIGKILL escalation — and the push wins by seconds.
signal.signal(signal.SIGTERM, signal.SIG_IGN)
import jax
import numpy as np
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.core.watchdog import monitored_step
from horovod_tpu.testing import faults
from jax.sharding import PartitionSpec as P
from jax.experimental import multihost_utils
try:
    from jax import shard_map
    _kw = {"check_vma": False}
except ImportError:
    from jax.experimental.shard_map import shard_map
    _kw = {"check_rep": False}

hvd.init()
mesh = hvd.mesh()
f = jax.jit(shard_map(lambda x: hvd.allreduce(x, hvd.Sum), mesh=mesh,
                      in_specs=P(hvd.RANK_AXIS), out_specs=P(), **_kw))

def psum_step(v):
    x = np.full((hvd.size(), 1), v, np.float32)
    gx = multihost_utils.host_local_array_to_global_array(
        x[hvd.rank():hvd.rank() + 1], mesh, P(hvd.RANK_AXIS))
    return float(np.asarray(multihost_utils.global_array_to_host_local_array(
        f(gx), mesh, P())).ravel()[0])

def chaos_step(v):
    # Step 9 of generation v2 is where the peer is killed. On gloo a dead
    # peer RESETS the survivor's collective (an error, not a hang), so to
    # exercise the rescue a real TPU pod needs — a survivor wedged in the
    # runtime with NO transport signal — this step blocks in-place on the
    # surviving rank; only the coordinator's failure push (through the
    # RESTARTED service) can abandon it.
    if v == 9.0 and os.environ.get("HOROVOD_ELASTIC_WORLD_VERSION") == "2":
        time.sleep(120)
    return psum_step(v)

mstep = monitored_step(chaos_step, what="coord_chaos_step")
state = elastic.ObjectState(step=0, total=0.0)

@elastic.run
def train(state):
    psum_step(0.0)   # compile outside any deadline
    while state.step < 12:
        faults.on_step(state.step, rank=hvd.rank())   # dies AT step top
        state.total += mstep(float(state.step))
        state.step += 1
        state.commit()
        time.sleep(0.25)
    return state.step

train(state)
print(json.dumps({"final_step": state.step, "size": hvd.size(),
                  "total": state.total}), flush=True)
"""


@pytest.mark.slow
@pytest.mark.integration
def test_coordinator_crash_restart_preserves_counters(tmp_path):
    """The control-plane tentpole end to end: generation 1 loses a worker
    (failure_seq -> 1), generation 2 has its COORDINATOR SERVICE crash
    mid-run; the driver rebuilds it from the journal on a fresh port with
    both monotonic counters intact, and a SECOND worker kill after the
    restart still reaches the survivor via the peer-liveness push — which
    only works if the restored failure_seq continued from 1, not 0. The
    final totals are only reachable if no generation was spuriously reset
    by the restart (version preserved) and every resume came from the
    newest commit."""
    import threading
    import time as _time
    from horovod_tpu import elastic
    from horovod_tpu.runner.settings import Settings

    script = tmp_path / "coord_chaos_worker.py"
    script.write_text(COORD_CHAOS_WORKER)
    logs = tmp_path / "logs"
    s = Settings(elastic=True, min_np=2, max_np=2,
                 hosts=[], host_discovery_script=None,
                 discovery_interval_s=0.25, start_timeout_s=60,
                 output_filename=str(logs),
                 env={"PYTHONPATH": REPO + os.pathsep +
                      os.environ.get("PYTHONPATH", ""),
                      "JAX_PLATFORMS": "cpu",
                      # the test process's 8-virtual-device XLA_FLAGS must
                      # not leak into workers: 1 device/proc => size == np
                      "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                      "HOROVOD_FAULT_SPEC":
                          "kill:rank=1,step=2;kill:rank=1,step=9",
                      "HOROVOD_FAULT_MARKER_DIR": str(tmp_path / "markers"),
                      # Peer push must be the rescue, not the stall window.
                      "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "0",
                      "HOROVOD_PEER_FAILURE_GRACE_SECONDS": "1",
                      "HOROVOD_LOG_LEVEL": "INFO"})
    d = elastic.ElasticDriver(
        s, [sys.executable, str(script)],
        discovery=elastic.FixedHostDiscovery({"localhost": 1,
                                              "127.0.0.2": 1}))

    obs = {}

    def _wait(pred, timeout_s=120.0):
        deadline = _time.monotonic() + timeout_s
        while _time.monotonic() < deadline:
            if pred():
                return True
            _time.sleep(0.05)
        return False

    def chaos():
        # 1. first kill journaled (generation 1 retires, seq -> 1)
        obs["kill1_seen"] = _wait(lambda: d._service.failure_seq >= 1)
        # 2. generation 2 (v2) running with both workers registered
        obs["gen2_up"] = _wait(
            lambda: d._service.version >= 2
            and len(d._service.registered_workers()) >= 2)
        old = d._service
        obs["old_port"] = old.port
        # 3. crash the coordinator service mid-generation
        old.simulate_crash()
        # 4. the driver's membership watch rebuilds it from the journal
        obs["rebuilt"] = _wait(
            lambda: d._service is not old and d._service.alive(), 30.0)
        obs["new_port"] = d._service.port
        obs["version_after_restart"] = d._service.version
        obs["seq_after_restart"] = d._service.failure_seq

    t = threading.Thread(target=chaos, daemon=True)
    t.start()
    code = d.run()
    t.join(timeout=10)

    assert code == 0
    assert obs.get("kill1_seen") and obs.get("gen2_up"), obs
    assert obs.get("rebuilt"), obs
    # Counters survived the crash: the rebuilt service continued from the
    # journal, it did not restart from zero.
    assert obs["new_port"] != obs["old_port"], obs
    assert obs["version_after_restart"] == 2, obs
    assert obs["seq_after_restart"] == 1, obs
    # np=2 throughout: gen v1 commits steps 0-1 (0+2=2); gen v2 resumes at
    # 2 and commits through step 8 (2 + 2*(2+..+8) = 72); gen v3 resumes
    # at 9 and finishes 9-11 (72+18+20+22 = 132) — i.e. every step ran
    # exactly once at world size 2, across two kills and one coordinator
    # restart, via three clean resumes from the newest commit.
    finals = []
    for f in sorted(logs.rglob("rank.*.stdout")):
        for line in f.read_text().splitlines():
            if line.startswith("{"):
                finals.append(json.loads(line))
    assert finals, list(logs.rglob("*"))
    done = [x for x in finals if x["final_step"] == 12]
    assert len(done) == 2, finals
    for x in done:
        assert x == {"final_step": 12, "size": 2, "total": 132.0}, finals
    # The second kill's rescue was the peer push THROUGH the restarted
    # coordinator (seq 2 > restored baseline 1) — logged by the survivor
    # of generation v2 before it took the RESTART exit.
    gen2_err = "".join(f.read_text()
                       for f in logs.rglob("generation.2/rank.*.stderr"))
    assert "peer failure notified" in gen2_err, gen2_err[-3000:]


@pytest.mark.slow
@pytest.mark.integration
def test_flaky_control_plane_during_elastic_resize(tmp_path):
    """Transient control-plane flakiness (a refused connect and a dropped
    reply, injected on exact RPC attempts) during a real elastic grow
    1 -> 2: the retrying client absorbs both faults and the resize
    completes — before the hardening, either fault read as 'no change'
    or a failed registration."""
    hosts_file = tmp_path / "grow_hosts"
    hosts_file.write_text("localhost:1\n")
    disco = tmp_path / "discover.sh"
    disco.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    disco.chmod(0o755)
    script = tmp_path / "grow_worker.py"
    script.write_text(GROW_WORKER)
    r = _run_hvdrun(["-np", "1", "--min-np", "1", "--max-np", "2",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "rpc_refuse:call=1;rpc_drop:call=3",
                     sys.executable, str(script)], timeout=300,
                    env_extra={"GROW_MARKER": str(tmp_path / "grown"),
                               "GROW_HOSTS_FILE": str(hosts_file),
                               "HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               "HOROVOD_LOG_LEVEL": "INFO"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    combined = r.stdout + r.stderr
    # Both faults actually fired at the client seam...
    assert "fault: rpc_refuse on coordinator rpc call 1" in combined
    assert "fault: rpc_drop on coordinator rpc call 3" in combined
    # ...and the resize still went through.
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 2, (lines, r.stdout)
    assert all(l["size"] == 2 and l["final_step"] == 12 for l in lines), lines
    assert "hosts gained" in combined


BADSIG_WORKER = """
import time
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
state = elastic.ObjectState(step=0)

@elastic.run
def train(state):
    while state.step < 6:
        time.sleep(0.3)
        state.step += 1
        state.commit()    # polls the coordinator -> exercises the client
    return state.step

train(state)
print("BADSIG-DONE", state.step, flush=True)
"""


@pytest.mark.slow
@pytest.mark.integration
def test_tampered_coordinator_reply_detected_in_real_run(tmp_path):
    """A tampered /world reply (valid transport, wrong HMAC) in a live
    elastic run is DETECTED and counted as a signature failure — distinct
    from a network error — and the retry recovers the poll, so the job
    still completes."""
    disco = tmp_path / "discover.sh"
    disco.write_text("#!/bin/sh\necho localhost:1\n")
    disco.chmod(0o755)
    script = tmp_path / "badsig_worker.py"
    script.write_text(BADSIG_WORKER)
    r = _run_hvdrun(["-np", "1", "--min-np", "1", "--max-np", "1",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "rpc_badsig:call=1",
                     sys.executable, str(script)], timeout=300,
                    env_extra={"HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               "HOROVOD_LOG_LEVEL": "INFO"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    combined = r.stdout + r.stderr
    assert "BADSIG-DONE 6" in r.stdout
    assert "fault: rpc_badsig on coordinator rpc call 1" in combined
    # The distinct signature-failure accounting (NOT the OSError path).
    assert "signature failure #1" in combined, combined[-3000:]
    assert "tampered or corrupt control-plane reply" in combined


LOST_WORKER = """
import time
import horovod_tpu as hvd
from horovod_tpu import elastic

hvd.init()
state = elastic.ObjectState(step=0)

@elastic.run
def train(state):
    while True:
        time.sleep(0.2)
        state.step += 1
        state.commit()

train(state)
"""


@pytest.mark.slow
@pytest.mark.integration
def test_persistent_coordinator_loss_escalates_worker(tmp_path):
    """A worker whose coordinator address points at nothing (the driver
    host died and never came back) escalates within
    HOROVOD_COORDINATOR_LOST_TIMEOUT_SECONDS: control-plane-lost is
    logged and the process takes the RESTART exit instead of polling a
    dead driver forever."""
    import socket
    import subprocess
    import time as _time
    from horovod_tpu.elastic import constants as C
    from horovod_tpu.runner import secret as _secret

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_addr = f"127.0.0.1:{probe.getsockname()[1]}"
    probe.close()

    script = tmp_path / "lost_worker.py"
    script.write_text(LOST_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        C.COORD_ADDR_ENV: dead_addr,
        C.WORLD_VERSION_ENV: "1",
        "HOROVOD_PROCESS_ID": "0",
        _secret.ENV_VAR: _secret.encode(_secret.make_secret_key()),
        C.COORD_LOST_TIMEOUT_ENV: "4",
        C.RPC_RETRIES_ENV: "1",
        C.RPC_TIMEOUT_ENV: "1",
    })
    t0 = _time.monotonic()
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=180, env=env)
    elapsed = _time.monotonic() - t0
    # RESTART exit: under a driver this requests a relaunch; standalone it
    # at least terminates the process instead of a silent poll-forever.
    assert r.returncode == C.RESTART_EXIT_CODE, \
        f"rc={r.returncode}\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "control plane lost" in r.stderr, r.stderr[-3000:]
    assert C.COORD_LOST_TIMEOUT_ENV in r.stderr, r.stderr[-3000:]
    # Bounded: the 4s window plus init/poll overhead, nowhere near the
    # 180s harness ceiling.
    assert elapsed < 120, f"escalation not bounded: {elapsed:.0f}s"


TELEMETRY_CHAOS_WORKER = """
import json
import os
import signal
import time
# Survivors must be rescued by their OWN HorovodInternalError path (which
# records the rescue event and dumps the flight ring) — not the driver's
# fate-sharing SIGTERM, whose default handler would die without dumping.
# A rank wedged inside the compiled runtime couldn't run a handler either.
signal.signal(signal.SIGTERM, signal.SIG_IGN)
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.core import watchdog
from horovod_tpu.optimizer import allgather_object
from horovod_tpu.testing import faults

hvd.init()
mon = watchdog.monitor()
state = elastic.ObjectState(step=0)

@elastic.run
def train(state):
    while state.step < 8:
        faults.on_step(state.step, rank=hvd.rank())   # victim dies here
        with mon.step_span("telemetry_chaos_step"):
            allgather_object(float(state.step))
        state.step += 1
        state.commit()   # piggybacks the metrics delta on the poll
        time.sleep(0.3)
    return state.step

train(state)
print(json.dumps({"final_step": state.step, "size": hvd.size()}),
      flush=True)
"""


@pytest.mark.integration
def test_chaos_kill_produces_cross_rank_incident_report(tmp_path):
    """The flight-recorder/incident tentpole end to end (docs/telemetry.md):
    3 real workers in a collective loop; rank 2 is SIGKILLed at step 5.
    Both survivors take HorovodInternalError, record a ``rescue`` event
    and dump their rings to HOROVOD_FLIGHT_DIR; the driver assembles
    ``incident_1.json`` joining the surviving dumps, the coordinator
    journal tail, and the coordinator's per-rank metrics — which carry
    the VICTIM's last-known step even though the victim never dumped.
    The relaunched generation then finishes cleanly."""
    flight_dir = tmp_path / "flight"
    disco = tmp_path / "discover.sh"
    disco.write_text(
        "#!/bin/sh\necho localhost:1\necho 127.0.0.2:1\necho 127.0.0.3:1\n")
    disco.chmod(0o755)
    script = tmp_path / "telemetry_chaos_worker.py"
    script.write_text(TELEMETRY_CHAOS_WORKER)
    r = _run_hvdrun(["-np", "3", "--min-np", "2", "--max-np", "3",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "kill:rank=2,step=5",
                     sys.executable, str(script)], timeout=300,
                    env_extra={"HOROVOD_FLIGHT_DIR": str(flight_dir),
                               "HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               # peer push is the rescue; stall window as
                               # fallback — both beat the 5s SIGKILL
                               "HOROVOD_PEER_FAILURE_GRACE_SECONDS": "1",
                               "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "4",
                               "HOROVOD_LOG_LEVEL": "INFO"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert lines and all(l["final_step"] == 8 for l in lines), r.stdout

    incidents = sorted(flight_dir.glob("incident_*.json"))
    assert incidents, list(flight_dir.iterdir())
    report = json.loads(incidents[0].read_text())
    assert report["failure_seq"] >= 1

    # ≥2 surviving ranks dumped, each with the rescue event; the victim
    # (rank 2) never dumped — it was SIGKILLed mid-step.
    survivors = {rk for rk in report["ranks"] if rk != "2"}
    assert len(survivors) >= 2, report["ranks"].keys()
    for rk in survivors:
        kinds = [ev["kind"] for ev in report["ranks"][rk]]
        assert "rescue" in kinds, (rk, kinds)
        assert "step_end" in kinds, (rk, kinds)
        assert kinds[-1] == "flight_dump", (rk, kinds)

    # the victim's last-known step survives via the coordinator's last
    # pushed metrics (commit() piggybacks the delta on the poll cadence)
    victim = report["coordinator_metrics"]["2"]
    assert victim["g"]["hvd_last_step"] >= 1.0, victim
    assert report["journal_tail"], report.keys()

    # the CLI renders the report (the post-mortem the operator reads)
    import io
    from horovod_tpu.tools.telemetry import cmd_incident
    buf = io.StringIO()
    assert cmd_incident(str(incidents[0]), out=buf) == 0
    text = buf.getvalue()
    assert "rescue" in text and "last_step" in text


SENTINEL_NAN_WORKER = """
import json
import numpy as np
import jax
import flax.linen as nn
import optax
import horovod_tpu as hvd
from horovod_tpu.optimizer import distributed
from horovod_tpu.testing import faults
from horovod_tpu.train import create_train_state, make_train_step
from jax.sharding import PartitionSpec as P
from jax.experimental import multihost_utils

hvd.init()
mesh = hvd.mesh()


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))


def xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


rng = np.random.RandomState(0)
xs = rng.randn(hvd.size() * 2, 4, 4, 1).astype(np.float32)
ys = rng.randint(0, 10, size=(hvd.size() * 2,))
lo = hvd.rank() * 2

model = MLP()
dopt = distributed(optax.sgd(0.05))
state = create_train_state(model, jax.random.PRNGKey(0), xs[:1], dopt)
# process-local init arrays -> host numpy, so the first global-mesh step
# call auto-replicates them (committed local buffers would be rejected)
state = jax.tree_util.tree_map(
    lambda a: np.asarray(jax.device_get(a)), state)
step = make_train_step(model, dopt, xent)      # HOROVOD_SENTINEL=1 engages
assert step.sentinel is not None

losses = []
for i in range(6):
    faults.on_step(i, rank=hvd.rank())
    # the nan fault splats NaN into THIS rank's host-local batch shard
    # before it is stitched into the global array: one corrupt rank
    local = faults.maybe_poison({"x": xs[lo:lo + 2]})["x"]
    gx = multihost_utils.host_local_array_to_global_array(
        local, mesh, P(hvd.RANK_AXIS))
    gy = multihost_utils.host_local_array_to_global_array(
        ys[lo:lo + 2], mesh, P(hvd.RANK_AXIS))
    state, loss = step(state, gx, gy)
    losses.append(float(np.asarray(jax.device_get(loss))))

print(json.dumps({
    "rank": hvd.rank(), "size": hvd.size(),
    "final_loss": losses[-1],
    "final_finite": bool(np.isfinite(losses[-1])),
    "nan_steps": int(sum(0 if np.isfinite(l) else 1 for l in losses)),
    "counters": step.sentinel.counters(),
}), flush=True)
"""


@pytest.mark.slow
@pytest.mark.integration
def test_sentinel_skips_nan_step_on_all_ranks(tmp_path):
    """Chaos ladder rung 1 end to end: 2 REAL processes, rank 0's batch
    shard is NaN-poisoned at step 3 (``nan`` fault). The in-graph health
    all_gather makes the verdict global, so BOTH ranks withhold the
    update (steps_skipped=1 everywhere — no desync between the corrupt
    rank and the clean one), training continues, and the final loss is
    finite."""
    import json as _json
    script = tmp_path / "sentinel_nan_worker.py"
    script.write_text(SENTINEL_NAN_WORKER)
    r = _run_hvdrun(["-np", "2", "-H", "localhost:1,127.0.0.1:1",
                     "--fault-spec", "nan:rank=0,step=3",
                     sys.executable, str(script)], timeout=360,
                    env_extra={"HOROVOD_SENTINEL": "1",
                               "HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers")})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [_json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 2, r.stdout
    for out in lines:
        assert out["size"] == 2
        assert out["final_finite"], out
        # the poisoned step itself reports a NaN loss (the forward ran);
        # every later step is finite because the update was withheld
        assert out["nan_steps"] == 1, out
        assert out["counters"]["steps_skipped"] == 1, out
        assert out["counters"]["rollbacks"] == 0, out
        assert out["counters"]["evictions"] == 0, out
    combined = r.stdout + r.stderr
    assert "sentinel: skip" in combined


SENTINEL_DESYNC_WORKER = """
import json
import os
import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
import optax
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.optimizer import distributed
from horovod_tpu.testing import faults
from horovod_tpu.train import TrainState, create_train_state, make_train_step
from jax.sharding import PartitionSpec as P
from jax.experimental import multihost_utils

hvd.init()


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(8)(x)


def xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def to_host(tree):
    return jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)), tree)


state = elastic.ObjectState(step=0, params=None, opt_state=None)


@elastic.run
def train(state):
    print("GEN-ENTRY step=%d size=%d version=%s" % (
        state.step, hvd.size(),
        os.environ.get("HOROVOD_ELASTIC_WORLD_VERSION")), flush=True)
    mesh = hvd.mesh()
    model = MLP()
    dopt = distributed(optax.sgd(0.05))
    rng = np.random.RandomState(0)
    xs = rng.randn(hvd.size(), 4, 4, 1).astype(np.float32)
    ys = rng.randint(0, 8, size=(hvd.size(),))
    init = create_train_state(model, jax.random.PRNGKey(0), xs[:1], dopt)
    params = state.params if state.params is not None \\
        else to_host(init.params)
    opt_state = state.opt_state if state.opt_state is not None \\
        else to_host(init.opt_state)
    # donate=False: state round-trips through host numpy every step so the
    # desync fault has a host-side replica to perturb
    step_fn = make_train_step(model, dopt, xent, donate=False)
    assert step_fn.sentinel is not None
    loss = float("nan")
    while state.step < 6:
        faults.on_step(state.step, rank=hvd.rank())
        # SDC injection: a finite eps shift on THIS rank's param replica
        # only -- invisible to isfinite/norm, caught only by the
        # cross-replica fingerprint lane
        params = faults.maybe_desync(params)
        ts = TrainState(jnp.int32(state.step), params, opt_state,
                        to_host(init.batch_stats))
        gx = multihost_utils.host_local_array_to_global_array(
            xs[hvd.rank():hvd.rank() + 1], mesh, P(hvd.RANK_AXIS))
        gy = multihost_utils.host_local_array_to_global_array(
            ys[hvd.rank():hvd.rank() + 1], mesh, P(hvd.RANK_AXIS))
        ts, loss = step_fn(ts, gx, gy)
        params, opt_state = to_host(ts.params), to_host(ts.opt_state)
        state.step += 1
        state.params, state.opt_state = params, opt_state
        state.commit()
    return float(np.asarray(jax.device_get(loss)))


final_loss = train(state)
from horovod_tpu.elastic import constants as C
_cas = os.path.join(os.environ[C.COMMIT_DIR_ENV], "cas")
print(json.dumps({
    "final_step": state.step, "size": hvd.size(),
    "final_loss": final_loss,
    "final_finite": bool(np.isfinite(final_loss)),
    "version": os.environ.get("HOROVOD_ELASTIC_WORLD_VERSION"),
    "manifests": sorted(f for f in os.listdir(_cas)
                        if f.startswith("manifest.")) if os.path.isdir(_cas)
    else [],
    "resume_latency_s": getattr(state, "_last_resume_latency_s", None),
}), flush=True)
"""


@pytest.mark.slow
@pytest.mark.integration
def test_sentinel_desync_evicts_minority_and_world_resumes(tmp_path):
    """Chaos ladder rung 3 end to end: 3 REAL elastic workers; at step 2
    the ``desync`` fault shifts rank 2's parameter replica by a finite
    eps (a silent-data-corruption stand-in — isfinite and grad-norm see
    nothing). The per-rank fingerprint lane exposes the divergence, every
    rank votes the strict minority (rank 2) corrupt, rank 2 exits
    EVICT_EXIT_CODE, the driver bans its host and relaunches the
    generation at np=2, and the survivors resume from the last
    blake2b-verified commit with the world version advanced."""
    import json as _json
    from horovod_tpu.elastic import constants as C
    disco = tmp_path / "discover.sh"
    disco.write_text(
        "#!/bin/sh\necho localhost:1\necho 127.0.0.2:1\necho 127.0.0.3:1\n")
    disco.chmod(0o755)
    script = tmp_path / "sentinel_desync_worker.py"
    script.write_text(SENTINEL_DESYNC_WORKER)
    r = _run_hvdrun(["-np", "3", "--min-np", "2", "--max-np", "3",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "desync:rank=2,step=2",
                     sys.executable, str(script)], timeout=420,
                    env_extra={"HOROVOD_SENTINEL": "1",
                               "HOROVOD_FAULT_MARKER_DIR":
                                   str(tmp_path / "fault_markers"),
                               "HOROVOD_LOG_LEVEL": "INFO"})
    assert r.returncode == 0, f"{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    lines = [_json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    # only the surviving generation's 2 ranks reach the final print
    assert len(lines) == 2, r.stdout
    for out in lines:
        assert (out["final_step"], out["size"]) == (6, 2), out
        assert out["final_finite"], out
    combined = r.stdout + r.stderr
    # eviction observed: the minority vote fired and the driver banned the
    # evicted rank's host (immediate ban, not strike accrual)
    assert "sentinel: evict" in combined
    assert "sentinel evict" in combined           # Blacklist.ban reason
    assert "(np=3)" in combined                   # generation 0
    assert "(np=2)" in combined                   # relaunched without rank 2
    # survivors resumed from a commit, not from scratch: generation 1
    # entered with committed progress (step >= 1)
    entries = [l for l in combined.splitlines() if l.startswith("GEN-ENTRY")]
    assert any("step=0 size=3" in e for e in entries), entries
    resumed = [e for e in entries if "size=2" in e]
    assert resumed and all("step=0" not in e for e in resumed), entries
    # the relaunched world carries an ADVANCED version: every size=2 entry
    # reports a strictly higher generation than every size=3 entry
    def _ver(e):
        return int(e.split("version=")[1])
    assert min(_ver(e) for e in resumed) > max(
        _ver(e) for e in entries if "size=3" in e), entries
    # and the resume itself came from the content-addressed store: the
    # survivors report published CAS manifests and a SUB-SECOND restore
    for out in lines:
        assert out["manifests"], out
        assert out["resume_latency_s"] is not None, out
        assert out["resume_latency_s"] < 1.0, out
    import re
    lat = [float(m) for m in
           re.findall(r"resume latency ([0-9.]+)s", combined)]
    assert lat and max(lat) < 1.0, (lat, combined[-2000:])


PREEMPT_CHAOS_WORKER = """
import json
import os
import time
import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.optimizer import allgather_object
from horovod_tpu.testing import faults

hvd.init()
N = int(os.environ["PREEMPT_STEPS"])
SLEEP = float(os.environ["PREEMPT_STEP_SLEEP"])
TRACE = os.environ["PREEMPT_TRACE_FILE"]
state = elastic.ObjectState(step=0, total=0.0)

@elastic.run
def train(state):
    while state.step < N:
        step = state.step
        vals = allgather_object(float(step))
        faults.on_step(step, rank=hvd.rank())   # preempt: SIGTERMs self,
        time.sleep(SLEEP)                       # then RUNS ON to the seam
        state.total += float(sum(vals))
        state.step = step + 1
        if hvd.rank() == 0:
            # committed-step ledger: "<step> <np>" per completed step —
            # the zero-lost-steps proof reads this back
            with open(TRACE, "a") as f:
                f.write("%d %d\\n" % (step, hvd.size()))
        state.commit()
    return state.step

train(state)
from horovod_tpu.elastic.state import notification_manager
_w = {}
if notification_manager._client is not None:
    _w = notification_manager._client.get_world() or {}
print(json.dumps({"final_step": state.step, "size": hvd.size(),
                  "failure_seq": _w.get("failure_seq"),
                  "preempts": _w.get("preempts")}), flush=True)
"""


@pytest.mark.integration
@pytest.mark.slow
def test_elastic_preempt_graceful_handoff_np3(tmp_path):
    """The ISSUE 20 acceptance chaos proof, end to end at np=3: the fault
    harness SIGTERMs rank 1 mid-generation. The victim runs on to its next
    commit seam (out-of-cadence commit), dumps its flight ring, posts the
    coordinator ``preempt`` notice (a VERSION bump, never a failure
    record), and exits with PREEMPT_EXIT_CODE. Survivors reset via the
    graceful membership push; the relaunched np=2 generation resumes from
    the victim's final commit (the per-step ledger proves zero lost
    steps); and once the cooldown expires the host is re-admitted —
    discovery re-offers it, the driver bumps the world, and the job
    FINISHES at np=3."""
    import time
    disco = tmp_path / "discover.sh"
    disco.write_text("#!/bin/sh\necho localhost:1\necho 127.0.0.2:1\n"
                     "echo 127.0.0.3:1\n")
    disco.chmod(0o755)
    script = tmp_path / "preempt_worker.py"
    script.write_text(PREEMPT_CHAOS_WORKER)
    trace = tmp_path / "step_trace"
    flight = tmp_path / "flight"
    n_steps = 60
    t0 = time.monotonic()
    r = _run_hvdrun(["-np", "3", "--min-np", "1", "--max-np", "3",
                     "--host-discovery-script", str(disco),
                     "--fault-spec", "preempt:rank=1,step=3",
                     sys.executable, str(script)], timeout=420,
                    env_extra={
                        "PREEMPT_STEPS": str(n_steps),
                        "PREEMPT_STEP_SLEEP": "0.35",
                        "PREEMPT_TRACE_FILE": str(trace),
                        "HOROVOD_FAULT_MARKER_DIR":
                            str(tmp_path / "fault_markers"),
                        "HOROVOD_FLIGHT_DIR": str(flight),
                        # cooldown must outlast the np=2 relaunch (so the
                        # shrunk generation EXISTS) yet expire while it
                        # still has steps left (so re-admission happens
                        # mid-run, not at rendezvous)
                        "HOROVOD_PREEMPT_COOLDOWN_SECONDS": "18",
                        "HOROVOD_PEER_FAILURE_GRACE_SECONDS": "2",
                        "HOROVOD_LOG_LEVEL": "INFO"})
    elapsed = time.monotonic() - t0
    assert r.returncode == 0, f"{r.stdout[-4000:]}\n{r.stderr[-4000:]}"
    combined = r.stdout + r.stderr

    # -- the victim's graceful exit (not a death) ----------------------------
    assert "fault: preempting self with SIGTERM" in combined, combined
    assert "preemption observed at the step seam (signal 15)" in combined
    assert "preempt flight ring dumped to" in combined
    assert "preemption handoff complete (signal 15)" in combined
    # coordinator recorded a preempt notice, on the VERSION counter
    assert "preempted (graceful)" in combined
    # driver mapped exit 76 to cooldown, explicitly NOT a blacklist strike
    assert "cooling down 18s before re-admission, no blacklist strike" \
        in combined, combined

    # -- never a failure record ----------------------------------------------
    # mark_failure was never called for the whole run: the final world's
    # monotonic failure_seq (printed by every surviving rank) is 0, and no
    # incident report was assembled.
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert len(lines) == 3, (lines, r.stdout)   # final generation is np=3
    for out in lines:
        assert out["final_step"] == n_steps and out["size"] == 3, lines
        assert out["failure_seq"] == 0, lines
    assert not list(flight.glob("incident_*.json")), \
        list(flight.iterdir())

    # -- zero lost steps across all three generations ------------------------
    ledger = [tuple(map(int, ln.split()))
              for ln in trace.read_text().splitlines()]
    steps = [s for s, _ in ledger]
    assert sorted(set(steps)) == list(range(n_steps)), sorted(set(steps))
    # generation 0 committed through the preempt step at np=3...
    by_step = {}
    for s, np_ in ledger:
        by_step.setdefault(s, []).append(np_)
    assert by_step[0] == [3], ledger[:6]
    # ...the shrunk generation resumed EXACTLY at the victim's final
    # commit (seam step 4 = preempt step 3 + 1): the first np=2 ledger
    # entry is step 4 — nothing replayed, nothing skipped
    np2_steps = [s for s, np_ in ledger if np_ == 2]
    assert np2_steps and min(np2_steps) == 4, ledger[:12]
    # ...and the tail ran at np=3 again after re-admission
    assert by_step[n_steps - 1] == [3], ledger[-6:]

    # -- re-admission after cooldown -----------------------------------------
    assert "preempt cooldown expired — eligible for re-admission" \
        in combined
    assert "hosts gained" in combined
    gens = [int(ln.split("(np=")[1].split(")")[0])
            for ln in combined.splitlines()
            if "launching generation" in ln]
    assert gens == [3, 2, 3], (gens, combined[-2000:])
    assert elapsed < 360, f"not bounded: {elapsed:.0f}s"
