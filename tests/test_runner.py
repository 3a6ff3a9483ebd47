"""Runner/CLI layer tests.

Reference parity: ``test/single/test_run.py`` (arg parsing, host-slot
parsing, command construction asserted WITHOUT executing ssh/mpirun) +
``test/integration/test_static_run.py`` (real localhost multi-process
launch) — SURVEY.md §4.
"""

import io
import os
import textwrap

import pytest

from horovod_tpu.runner import (HostInfo, Settings, check_build,
                                get_host_assignments, parse_host_files,
                                parse_hosts, parse_settings)
from horovod_tpu.runner.exec_run import (get_run_env, get_ssh_command,
                                         is_local)
from horovod_tpu.runner import secret


# --- host parsing -----------------------------------------------------------

def test_parse_hosts():
    hs = parse_hosts("a:4,b:2")
    assert hs == [HostInfo("a", 4), HostInfo("b", 2)]


@pytest.mark.parametrize("bad", ["", "a", "a:0", "a:-1", "a:b", "a 4"])
def test_parse_hosts_rejects(bad):
    with pytest.raises(ValueError):
        parse_hosts(bad)


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hf"
    f.write_text(textwrap.dedent("""\
        # comment
        node1 slots=4
        node2   slots=2
        node3
    """))
    assert parse_host_files(str(f)) == "node1:4,node2:2,node3:1"


def test_host_assignments_full():
    a = get_host_assignments(parse_hosts("a:4,b:4"))
    assert len(a) == 2
    assert a[0].first_rank == 0 and a[0].local_size == 4
    assert a[1].first_rank == 4 and a[1].local_size == 4
    assert a[1].process_id == 1 and a[1].num_processes == 2
    assert a[0].world_size == 8
    assert [s.rank for s in a[1].slots] == [4, 5, 6, 7]
    assert all(s.cross_rank == 1 and s.local_size == 4 for s in a[1].slots)


def test_host_assignments_np_caps_and_overflows():
    a = get_host_assignments(parse_hosts("a:4,b:4"), np_=5)
    assert len(a) == 2 and a[1].local_size == 1 and a[0].world_size == 5
    a = get_host_assignments(parse_hosts("a:4,b:4"), np_=4)
    assert len(a) == 1 and a[0].num_processes == 1
    with pytest.raises(ValueError):
        get_host_assignments(parse_hosts("a:2"), np_=3)


# --- env / command construction --------------------------------------------

def test_get_run_env_wiring():
    a = get_host_assignments(parse_hosts("localhost:2,h2:2"))[1]
    env = get_run_env(a, Settings(), "10.0.0.1:29400",
                      secret_key=b"\x01" * 32)
    assert env["HOROVOD_COORDINATOR_ADDR"] == "10.0.0.1:29400"
    assert env["HOROVOD_NUM_PROCESSES"] == "2"
    assert env["HOROVOD_PROCESS_ID"] == "1"
    assert env["HOROVOD_SIZE"] == "4"
    assert env["HOROVOD_LOCAL_SIZE"] == "2"
    assert env["HOROVOD_FIRST_RANK"] == "2"
    assert env[secret.ENV_VAR] == "01" * 32
    # forwarded prefixes (conftest exported these)
    assert "JAX_PLATFORMS" in env


def test_ssh_command_construction():
    a = get_host_assignments(parse_hosts("remote1:4"))[0]
    s = Settings(ssh_port=2222, ssh_identity_file="/k.pem")
    env = {"HOROVOD_COORDINATOR_ADDR": "c:1", "SECRET_PATH": "/x",
           "XLA_FLAGS": "--foo bar"}
    line = get_ssh_command(a, ["python", "train.py", "--lr", "0.1"], env, s,
                           cwd="/work dir")
    assert line.startswith("ssh -o PasswordAuthentication=no "
                           "-o StrictHostKeyChecking=no -p 2222 -i /k.pem "
                           "remote1 ")
    # The remote payload is one shell-quoted argument; parse it back.
    import shlex
    payload = shlex.split(line)[-1]
    assert payload.startswith("cd '/work dir' && env ")
    assert "HOROVOD_COORDINATOR_ADDR=c:1" in payload
    assert "XLA_FLAGS='--foo bar'" in payload
    assert "SECRET_PATH" not in payload       # non-forwarded key stays home
    assert payload.endswith("python train.py --lr 0.1")


def test_is_local():
    assert is_local("localhost") and is_local("127.0.0.1")
    assert not is_local("tpu-host-7")


def test_ssh_secret_on_stdin_not_cmdline():
    a = get_host_assignments(parse_hosts("remote1:4"))[0]
    s = Settings(env={"OMP_NUM_THREADS": "8"})
    key = b"\x02" * 32
    env = get_run_env(a, s, "c:1", secret_key=key)
    line = get_ssh_command(a, ["python", "t.py"], env, s,
                           secret_on_stdin=True)
    assert secret.encode(key) not in line          # never on the wire line
    assert "IFS= read -r HOROVOD_SECRET_KEY" in line
    assert "OMP_NUM_THREADS=8" in line             # Settings.env forwarded


def test_default_coordinator_addr():
    from horovod_tpu.runner.exec_run import default_coordinator_addr
    local = get_host_assignments(parse_hosts("localhost:2"))
    addr = default_coordinator_addr(local, Settings())
    host, port = addr.rsplit(":", 1)
    assert host == "127.0.0.1" and 1024 <= int(port) <= 65535
    remote = get_host_assignments(parse_hosts("tpu-a:4,tpu-b:4"))
    assert default_coordinator_addr(
        remote, Settings(coordinator_port=12345)) == "tpu-a:12345"
    assert default_coordinator_addr(remote, Settings()) == "tpu-a:29400"


def test_run_rejects_oversized_function_for_remote_transport():
    """Multi-host runner.run() ships the fn via the ssh-forwarded env
    (r4 — the NotImplementedError is gone); a closure beyond the 1 MiB
    total env-transport ceiling (chunked across 96 KiB vars — Linux's
    per-string MAX_ARG_STRLEN) fails loudly with guidance, BEFORE
    launching."""
    from horovod_tpu.runner import run
    big = bytes(1100 * 1024)  # closure > 1MiB base64 ceiling
    with pytest.raises(RuntimeError, match="env transport limit"):
        run(lambda: len(big), np=2, hosts="tpu-a:1,tpu-b:1")


def test_stdin_env_keys_orders_function_chunks():
    """Both sides of the stdin protocol derive the SAME ordered key list
    from the env: base key first, numbered overflow chunks in index order
    (10 after 9, not lexicographic), non-numeric suffixes ignored."""
    from horovod_tpu.runner.exec_run import stdin_env_keys, stdin_env_lines
    env = {f"HOROVOD_RUN_FUNC_B64_{i}": f"c{i}" for i in (10, 2, 1, 9)}
    env["HOROVOD_RUN_FUNC_B64"] = "c0"
    env["HOROVOD_RUN_FUNC_B64_x"] = "not-a-chunk"
    ks = stdin_env_keys(env)
    assert ks == ["HOROVOD_RUN_FUNC_B64"] + [
        f"HOROVOD_RUN_FUNC_B64_{i}" for i in (1, 2, 9, 10)]
    assert stdin_env_lines(env) == ["c0", "c1", "c2", "c9", "c10"]


# --- CLI parsing ------------------------------------------------------------

def test_parse_settings_static():
    s, cmd = parse_settings(["-np", "8", "-H", "a:4,b:4", "--verbose",
                             "python", "train.py"])
    assert s.num_proc == 8 and len(s.hosts) == 2 and not s.elastic
    assert cmd == ["python", "train.py"]


def test_parse_settings_elastic():
    s, cmd = parse_settings(["--min-np", "2", "--max-np", "8",
                             "--host-discovery-script", "./d.sh",
                             "--slots-per-host", "4", "python", "t.py"])
    assert s.elastic and s.min_np == 2 and s.max_np == 8
    assert s.host_discovery_script == "./d.sh" and s.slots_per_host == 4
    assert cmd == ["python", "t.py"]


def test_parse_settings_accepts_reference_transport_flags(capsys):
    # Reference drop-in compat: --gloo/--mpi are accepted and ignored with
    # a warning (one transport here).
    s, cmd = parse_settings(["-np", "2", "-H", "localhost:2", "--gloo",
                             "python", "t.py"])
    assert cmd == ["python", "t.py"]
    assert "ignored" in capsys.readouterr().err
    s, cmd = parse_settings(["-np", "2", "-H", "localhost:2", "--mpi",
                             "--mpi-args", "-x FOO", "python", "t.py"])
    assert cmd == ["python", "t.py"]


def test_parse_settings_requires_command():
    with pytest.raises(SystemExit):
        parse_settings(["-np", "2"])


def test_parse_settings_validation():
    with pytest.raises(ValueError):
        parse_settings(["--min-np", "8", "--max-np", "2",
                        "--host-discovery-script", "d", "x"])


def test_check_build_output():
    buf = io.StringIO()
    check_build(file=buf)
    out = buf.getvalue()
    assert "XLA" in out and "elastic" in out and "join" in out


# --- real localhost integration (reference: test_static_run.py) -------------

@pytest.mark.integration
def test_run_function_two_processes():
    """Launch 2 host processes on localhost through the full runner stack;
    each joins the JAX coordination service and reports its coordinates."""
    from horovod_tpu.runner import run

    def fn():
        import jax
        import horovod_tpu as hvd
        return (hvd.cross_rank(), hvd.cross_size(), hvd.size(),
                jax.process_index())

    # Two distinct -H entries -> two host processes on localhost (the
    # reference's "localhost slots as fake hosts" trick, SURVEY.md §4).
    results = run(fn, np=2, hosts="localhost:1,localhost:1",
                  settings=Settings(num_proc=2, start_timeout_s=300))
    assert len(results) == 2
    assert results[0][:2] == (0, 2) and results[1][:2] == (1, 2)
    # 2 processes × 8 forced-cpu devices each
    assert results[0][2] == results[1][2] == 16
    assert [r[3] for r in results] == [0, 1]


def test_run_func_blob_travels_on_stdin_not_cmdline():
    """The cloudpickled fn may capture credentials: like the HMAC secret,
    it must never appear in the ssh command line (``ps`` on either host)
    — the remote shell reads it from stdin instead."""
    from horovod_tpu.runner.exec_run import (get_ssh_command,
                                             stdin_env_lines)
    from horovod_tpu.runner.hosts import HostAssignment
    a = HostAssignment(hostname="tpu-b", process_id=1, num_processes=2,
                       world_size=2, local_size=1, first_rank=1)
    env = {"HOROVOD_RUN_FUNC_B64": "U0VDUkVUX0JMT0I=",
           "HOROVOD_RUN_RESULTS_DIR": "/tmp/x",
           "HOROVOD_PROCESS_ID": "1"}
    s = Settings(num_proc=2)
    line = get_ssh_command(a, ["python", "-m",
                               "horovod_tpu.runner.run_task"], env, s)
    assert "U0VDUkVUX0JMT0I=" not in line
    assert "read -r HOROVOD_RUN_FUNC_B64" in line
    assert "export HOROVOD_RUN_FUNC_B64" in line
    # the results dir (not secret) still rides the wire env
    assert "HOROVOD_RUN_RESULTS_DIR=/tmp/x" in line
    assert stdin_env_lines(env) == ["U0VDUkVUX0JMT0I="]


@pytest.mark.integration
def test_run_function_multi_host_env_transport(monkeypatch):
    """VERDICT r3 #5: the function API works multi-host. Loopback hosts
    (localhost + 127.0.0.2 — distinct hosts per the launcher's model)
    with the remote transport FORCED: the cloudpickled fn rides the env,
    results allgather over the engine, rank 0 writes one blob. Also:
    a failing worker's traceback must surface through the same path."""
    from horovod_tpu.runner import run

    monkeypatch.setenv("HOROVOD_RUN_REMOTE_TRANSPORT", "1")

    def fn(scale):
        import horovod_tpu as hvd
        return {"rank": hvd.cross_rank(), "val": scale * hvd.cross_size()}

    results = run(fn, args=(10,), np=2, hosts="localhost:1,127.0.0.2:1",
                  settings=Settings(num_proc=2, start_timeout_s=300))
    assert results == [{"rank": 0, "val": 20}, {"rank": 1, "val": 20}]

    # a closure above one MAX_ARG_STRLEN chunk (reassembled from numbered
    # env vars on the worker side — exec_run.stdin_env_keys order)
    import hashlib
    big = bytes(range(256)) * 1200  # ~300 KiB -> ~400 KiB base64, 5 chunks
    want = hashlib.sha256(big).hexdigest()

    def big_fn():
        import hashlib as h
        import horovod_tpu as hvd
        return hvd.cross_rank(), h.sha256(big).hexdigest()

    big_results = run(big_fn, np=2, hosts="localhost:1,127.0.0.2:1",
                      settings=Settings(num_proc=2, start_timeout_s=300))
    assert big_results == [(0, want), (1, want)]

    # a failing worker's traceback must surface through the SAME forced
    # env/one-blob transport (the monkeypatched env var is still live here)
    def boom():
        raise ValueError("deliberate-worker-error")

    with pytest.raises(RuntimeError, match="deliberate-worker-error"):
        run(boom, np=2, hosts="localhost:1,127.0.0.2:1",
            settings=Settings(num_proc=2, start_timeout_s=300))


@pytest.mark.integration
def test_run_function_failure_per_rank_files():
    """The DEFAULT transport (all-local hosts, no env forcing) reports a
    failing worker via its per-rank result.N.pkl — the load_result file
    branch, distinct from the env/one-blob path tested above."""
    from horovod_tpu.runner import run

    def boom():
        raise ValueError("deliberate-worker-error")

    with pytest.raises(RuntimeError, match="deliberate-worker-error"):
        run(boom, np=2, hosts="localhost:1,127.0.0.2:1",
            settings=Settings(num_proc=2, start_timeout_s=300))


@pytest.mark.integration
def test_run_function_elastic_fixed_hosts():
    """min_np routes runner.run() through the ElasticDriver generation
    loop (the reference's horovod.run accepts the elastic knobs too):
    fixed discovery from hosts=, one successful generation, results via
    the forced one-blob transport sized to that generation's world."""
    from horovod_tpu.runner import run

    def fn():
        import horovod_tpu as hvd
        return ("gen", hvd.cross_rank(), hvd.cross_size())

    results = run(fn, min_np=2, hosts="localhost:1,127.0.0.2:1",
                  settings=Settings(num_proc=2, start_timeout_s=300))
    assert results == [("gen", 0, 2), ("gen", 1, 2)]


def test_get_run_env_blocklist_and_timeout(monkeypatch):
    """Full environ is inherited minus the blocklist; --start-timeout is
    exported for worker-side rendezvous bounding."""
    a = get_host_assignments(parse_hosts("localhost:1"))[0]
    monkeypatch.setenv("HVD_TEST_RANDOM_VAR", "yes")
    monkeypatch.setenv("SSH_AUTH_SOCK", "/tmp/agent.sock")
    env = get_run_env(a, Settings(start_timeout_s=42.0), "c:1")
    assert env["HVD_TEST_RANDOM_VAR"] == "yes"     # blocklist, not allowlist
    assert "SSH_AUTH_SOCK" not in env
    assert secret.ENV_VAR not in env
    assert env["HOROVOD_START_TIMEOUT"] == "42.0"


def test_coordinator_addr_routable_for_mixed_job(monkeypatch):
    """A local process 0 with remote peers must advertise a routable
    address, never the loopback bind host."""
    from horovod_tpu.runner import exec_run
    monkeypatch.setattr(exec_run, "routable_local_addr",
                        lambda remote: "10.0.0.5")
    mixed = get_host_assignments(parse_hosts("localhost:2,tpu-b:2"))
    addr = exec_run.default_coordinator_addr(mixed, Settings())
    host, port = addr.rsplit(":", 1)
    assert host == "10.0.0.5"
    assert 1024 <= int(port) <= 65535


def test_routable_local_addr_never_loopback():
    """Whatever the probe path, a loopback answer must not be returned
    unless there is literally nothing else (then the hostname is)."""
    from horovod_tpu.runner.exec_run import routable_local_addr
    addr = routable_local_addr("host-that-does-not-resolve.invalid")
    assert not addr.startswith("127.")


def test_launch_job_surfaces_spawn_failure(tmp_path):
    """A missing binary must yield a non-zero job exit, not silent success."""
    from horovod_tpu.runner.exec_run import launch_job
    a = get_host_assignments(parse_hosts("localhost:1"))
    code = launch_job(a, ["/nonexistent/binary-xyz"], Settings(),
                      coordinator_addr="127.0.0.1:1")
    assert code != 0


# ---------------- cluster detection + config file ----------------

def test_slurm_nodelist_expansion(monkeypatch):
    from horovod_tpu.runner import clusters
    monkeypatch.setattr(clusters.subprocess, "run",
                        lambda *a, **k: (_ for _ in ()).throw(OSError()))
    assert clusters._expand_slurm_nodelist("tpu-[001-003,005],head") == [
        "tpu-001", "tpu-002", "tpu-003", "tpu-005", "head"]


def test_slurm_detect_hosts(monkeypatch):
    from horovod_tpu.runner import clusters
    monkeypatch.setenv("SLURM_JOB_ID", "42")
    monkeypatch.setenv("SLURM_JOB_NODELIST", "n[1-3]")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "4(x2),2")
    monkeypatch.setattr(clusters.subprocess, "run",
                        lambda *a, **k: (_ for _ in ()).throw(OSError()))
    assert clusters.detect_hosts() == "n1:4,n2:4,n3:2"


def test_lsf_detect_hosts(monkeypatch):
    from horovod_tpu.runner import clusters
    monkeypatch.delenv("SLURM_JOB_ID", raising=False)
    monkeypatch.setenv("LSB_JOBID", "7")
    monkeypatch.setenv("LSB_MCPU_HOSTS", "a 4 b 2")
    assert clusters.LSFUtils.using_lsf()
    assert clusters.LSFUtils.get_num_processes() == 6
    assert clusters.detect_hosts() == "a:4,b:2"


def test_launch_uses_scheduler_hosts(monkeypatch):
    from horovod_tpu.runner.launch import parse_settings
    monkeypatch.setenv("SLURM_JOB_ID", "42")
    monkeypatch.setenv("SLURM_JOB_NODELIST", "n[1-2]")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "4(x2)")
    from horovod_tpu.runner import clusters
    monkeypatch.setattr(clusters.subprocess, "run",
                        lambda *a, **k: (_ for _ in ()).throw(OSError()))
    s, cmd = parse_settings(["-np", "8", "python", "train.py"])
    assert [(h.hostname, h.slots) for h in s.hosts] == [("n1", 4), ("n2", 4)]
    assert cmd == ["python", "train.py"]


def test_config_file_defaults_cli_wins(tmp_path):
    from horovod_tpu.runner.launch import parse_settings
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("np: 4\nverbose: 2\nstart-timeout: 33\n")
    s, cmd = parse_settings(["--config-file", str(cfg), "-np", "8",
                             "-H", "localhost:8", "python", "t.py"])
    assert s.num_proc == 8          # CLI beats file
    assert s.verbose == 2           # file supplies default
    assert s.start_timeout_s == 33
    assert cmd == ["python", "t.py"]


def test_config_file_unknown_key_rejected(tmp_path):
    import pytest
    from horovod_tpu.runner.launch import parse_settings
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("nonsense_knob: 1\n")
    with pytest.raises(SystemExit, match="unknown keys"):
        parse_settings(["--config-file", str(cfg), "-np", "2", "x"])


def test_timeline_start_stop(tmp_path):
    import json
    import horovod_tpu as hvd
    path = tmp_path / "tl.json"
    hvd.start_timeline(str(path), mark_cycles=True)
    ctx = hvd.core.context()
    ctx.timeline.activity_start("t0", "ALLREDUCE")
    ctx.timeline.activity_end("t0", "ALLREDUCE")
    hvd.stop_timeline()
    assert ctx.timeline is None
    evs = json.loads(path.read_text())
    assert [e["ph"] for e in evs] == ["B", "E"]
    assert not hvd.mpi_threads_supported()


def test_config_file_not_hijacked_from_command(tmp_path):
    """A --config-file flag belonging to the launched training script must
    reach that script, not the launcher."""
    from horovod_tpu.runner.launch import parse_settings
    s, cmd = parse_settings(["-np", "2", "-H", "localhost:2",
                             "python", "train.py",
                             "--config-file", "training.yaml"])
    assert cmd == ["python", "train.py", "--config-file", "training.yaml"]
    assert s.num_proc == 2


def test_config_file_count_flag_merges_not_stacks(tmp_path):
    from horovod_tpu.runner.launch import parse_settings
    cfg = tmp_path / "c.yaml"
    cfg.write_text("verbose: 2\n")
    # explicit -v on the CLI wins outright (no 2+1 stacking)
    s, _ = parse_settings(["--config-file", str(cfg), "-v", "-np", "2",
                           "python", "x.py"])
    assert s.verbose == 1
    # absent from the CLI: the file value applies
    s2, _ = parse_settings(["--config-file", str(cfg), "-np", "2",
                            "python", "x.py"])
    assert s2.verbose == 2


def test_parse_settings_tuning_flags_map_to_worker_env():
    s, cmd = parse_settings([
        "-np", "2", "-H", "localhost:2",
        "--fusion-threshold-mb", "128", "--timeline-filename", "/tmp/t.json",
        "--timeline-mark-cycles", "--autotune",
        "--autotune-log-file", "/tmp/a.csv", "--log-level", "DEBUG",
        "--no-stall-check", "--stall-check-warning-time-seconds", "30",
        "python", "t.py"])
    assert cmd == ["python", "t.py"]
    assert s.env["HOROVOD_FUSION_THRESHOLD"] == str(128 << 20)
    assert s.env["HOROVOD_TIMELINE"] == "/tmp/t.json"
    assert s.env["HOROVOD_TIMELINE_MARK_CYCLES"] == "1"
    assert s.env["HOROVOD_AUTOTUNE"] == "1"
    assert s.env["HOROVOD_AUTOTUNE_LOG"] == "/tmp/a.csv"
    assert s.env["HOROVOD_LOG_LEVEL"] == "DEBUG"
    assert s.env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
    assert s.env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "30.0"
    # no accidental entries for flags not given
    assert "HOROVOD_CYCLE_TIME" not in s.env


def test_parse_settings_no_tuning_flags_empty_env():
    s, _ = parse_settings(["-np", "1", "-H", "localhost:1", "python", "x"])
    assert s.env == {}


def test_config_file_accepts_documented_tuning_keys(tmp_path):
    cfg = tmp_path / "c.yaml"
    cfg.write_text("stall-check-warning-time-seconds: 30\n"
                   "fusion-threshold-mb: \"128\"\n")   # quoted on purpose
    s, _ = parse_settings(["--config-file", str(cfg), "-np", "1",
                           "-H", "localhost:1", "python", "x"])
    assert s.env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "30.0"
    assert s.env["HOROVOD_FUSION_THRESHOLD"] == str(128 << 20)


def test_timeline_path_is_per_worker_on_multihost():
    from horovod_tpu.runner.exec_run import get_run_env
    from horovod_tpu.runner.hosts import HostAssignment

    s = Settings(num_proc=2, env={"HOROVOD_TIMELINE": "/tmp/t.json"})
    a1 = HostAssignment(hostname="a", process_id=1, num_processes=2,
                        first_rank=1, local_size=1, world_size=2)
    env = get_run_env(a1, s, "a:1")
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.rank1.json"
    a0 = HostAssignment(hostname="a", process_id=0, num_processes=1,
                        first_rank=0, local_size=1, world_size=1)
    env0 = get_run_env(a0, s, "a:1")
    assert env0["HOROVOD_TIMELINE"] == "/tmp/t.json"   # single proc: as-is


def test_run_function_accepts_hostfile(tmp_path):
    """run(hostfile=...) parses the mpirun-style file like the CLI's
    --hostfile (reference run() accepts hostfile= too). One result per
    HOST process — the launcher's one-process-per-host model."""
    from horovod_tpu.runner import run
    hf = tmp_path / "hosts.txt"
    hf.write_text("localhost slots=1\n127.0.0.2 slots=1\n")
    results = run(lambda: 7, np=2, hostfile=str(hf),
                  settings=Settings(num_proc=2, start_timeout_s=300))
    assert results == [7, 7]
