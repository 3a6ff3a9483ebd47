"""Per-host worker launch: env wiring + command construction + job control.

Reference parity: ``horovod/runner/gloo_run.py`` + ``mpi_run.py``
(SURVEY.md §3.3). The reference execs one worker per slot over ssh with
``HOROVOD_RANK/SIZE/GLOO_RENDEZVOUS_ADDR`` env; here one worker per *host*
is execed with the JAX coordination-service coordinates
(``HOROVOD_COORDINATOR_ADDR/NUM_PROCESSES/PROCESS_ID`` — consumed by
``hvd.init()``, core/context_api.py), which replaces the Gloo HTTP
rendezvous (§2.7). Command construction is pure (testable without ssh,
reference test_run.py pattern); job control kills every host's tree on
first failure.
"""

from __future__ import annotations

import os
import shlex
import socket
import sys
import threading
from typing import Dict, List, Optional, Sequence

from . import secret
from .hosts import HostAssignment
from .safe_shell_exec import execute
from .settings import Settings

#: env prefixes forwarded over ssh to REMOTE workers (host-specific vars like
#: PATH/HOME/TMPDIR must not cross hosts; the remote shell supplies its own).
FORWARD_PREFIXES = ("HOROVOD_", "XLA_", "JAX_", "TPU_", "LIBTPU_", "PYTHON")

#: env vars never forwarded to any worker (reference: env_util.is_exportable
#: blocklist). Local workers otherwise inherit the full launcher environ.
BLOCKED_ENV = ("HOROVOD_SECRET_KEY", "BASH_FUNC_", "OLDPWD", "SSH_AUTH_SOCK",
               "SSH_CONNECTION", "SSH_CLIENT", "SSH_TTY")


def find_free_port(bind_host: str = "127.0.0.1") -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((bind_host, 0))
        return s.getsockname()[1]


def assignment_env(a: HostAssignment, coordinator_addr: str,
                   start_timeout_s: float) -> Dict[str, str]:
    """The HOROVOD_* env contract for one host assignment — the single
    source of truth shared by the ssh, Ray and Spark launchers (the
    reference spreads the same contract across gloo_run/mpi_run/spark)."""
    return {
        "HOROVOD_COORDINATOR_ADDR": coordinator_addr,
        "HOROVOD_START_TIMEOUT": str(start_timeout_s),
        "HOROVOD_NUM_PROCESSES": str(a.num_processes),
        "HOROVOD_PROCESS_ID": str(a.process_id),
        "HOROVOD_SIZE": str(a.world_size),
        "HOROVOD_LOCAL_SIZE": str(a.local_size),
        "HOROVOD_FIRST_RANK": str(a.first_rank),
        "HOROVOD_HOSTNAME": a.hostname,
    }


def get_run_env(a: HostAssignment, settings: Settings,
                coordinator_addr: str, secret_key: Optional[bytes] = None
                ) -> Dict[str, str]:
    """Env for host-process ``a`` (a pure function of the assignment).

    The HMAC secret only enters the env on the LOCAL spawn path (a child's
    environ is not world-readable); the ssh path delivers it over stdin
    instead — see :func:`get_ssh_command` — so it never appears in a
    command line / ``ps`` output.
    """
    # Local spawn inherits the full launcher environ minus a blocklist
    # (reference: env_util.is_exportable excludes, not includes); the ssh
    # path later narrows this to FORWARD_PREFIXES — see get_ssh_command.
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(BLOCKED_ENV)}
    env.update(settings.env)
    if env.get("HOROVOD_TIMELINE") and a.num_processes > 1:
        # One trace file PER WORKER: multi-host runs over a shared FS would
        # otherwise truncate and interleave one file into invalid JSON.
        root, ext = os.path.splitext(env["HOROVOD_TIMELINE"])
        env["HOROVOD_TIMELINE"] = f"{root}.rank{a.process_id}{ext or '.json'}"
    env.update(assignment_env(a, coordinator_addr, settings.start_timeout_s))
    if secret_key is not None:
        env[secret.ENV_VAR] = secret.encode(secret_key)
    return env


def quoted_env_assignments(env: Dict[str, str],
                           keys: Optional[Sequence[str]] = None) -> str:
    ks = keys if keys is not None else sorted(env)
    return " ".join(f"{k}={shlex.quote(env[k])}" for k in ks if k in env)


#: env keys that must never ride the ssh command line (visible in
#: ``ps``/``/proc/*/cmdline`` on both hosts) — delivered over stdin like
#: the HMAC secret. HOROVOD_RUN_FUNC_B64 is the cloudpickled user
#: function for runner.run()'s multi-host mode: its closure may capture
#: credentials.
STDIN_ENV_KEYS = ("HOROVOD_RUN_FUNC_B64",)

#: numbered overflow chunks of HOROVOD_RUN_FUNC_B64: Linux caps ONE
#: execve env string at 128 KiB (MAX_ARG_STRLEN), so a large pickled fn
#: is split across several vars — each side of the stdin protocol
#: derives the same ordered key list from the env via stdin_env_keys().
_STDIN_CHUNK_PREFIX = "HOROVOD_RUN_FUNC_B64_"


def stdin_env_keys(env: Dict[str, str]) -> List[str]:
    """The ordered stdin-delivered keys for this env: the fixed
    ``STDIN_ENV_KEYS`` plus any numbered overflow chunks, in index order
    — the writer (:func:`stdin_env_lines`) and the remote read sequence
    (:func:`get_ssh_command`) must agree exactly."""
    keys = [k for k in STDIN_ENV_KEYS if k in env]
    keys += sorted((k for k in env
                    if k.startswith(_STDIN_CHUNK_PREFIX)
                    and k[len(_STDIN_CHUNK_PREFIX):].isdigit()),
                   key=lambda k: int(k[len(_STDIN_CHUNK_PREFIX):]))
    return keys


def ssh_base_command(settings: Settings) -> List[str]:
    """The launcher's ssh invocation prefix — ONE definition shared by
    the worker launch and the results fetch (``runner.api``)."""
    ssh = ["ssh", "-o", "PasswordAuthentication=no",
           "-o", "StrictHostKeyChecking=no"]
    if settings.ssh_port:
        ssh += ["-p", str(settings.ssh_port)]
    if settings.ssh_identity_file:
        ssh += ["-i", settings.ssh_identity_file]
    if settings.extra_ssh_args:
        ssh += settings.extra_ssh_args.split()
    return ssh


def stdin_env_lines(env: Dict[str, str]) -> List[str]:
    """Values the remote shell reads from stdin, in the FIXED order
    matching :func:`get_ssh_command`'s read sequence."""
    return [env[k] for k in stdin_env_keys(env)]


def get_ssh_command(a: HostAssignment, command: Sequence[str],
                    env: Dict[str, str], settings: Settings,
                    cwd: Optional[str] = None,
                    secret_on_stdin: bool = False) -> str:
    """Build the ssh line for a remote host (reference: gloo_run.py
    _exec_command_fn). Returned as a string for assertion-style tests.

    ``secret_on_stdin``: the remote shell reads ``HOROVOD_SECRET_KEY``
    from its stdin (the launcher writes it via ``execute(stdin_data=...)``)
    so the key never appears in ``ps``/``/proc/*/cmdline`` on either side;
    any ``STDIN_ENV_KEYS`` present in the env follow on later stdin lines
    for the same reason.
    """
    ssh = ssh_base_command(settings)
    ssh.append(a.hostname)
    inner = ""
    if cwd:
        inner += f"cd {shlex.quote(cwd)} && "
    if secret_on_stdin:
        inner += "IFS= read -r HOROVOD_SECRET_KEY && " \
                 "export HOROVOD_SECRET_KEY && "
    stdin_keys = stdin_env_keys(env)
    for k in stdin_keys:
        inner += f"IFS= read -r {k} && export {k} && "
    # Launcher-owned env goes over the wire: forwarded prefixes plus every
    # key the user put in Settings.env (same set a local worker receives);
    # the remote shell keeps its own PATH/HOME. The secret and the
    # stdin-delivered keys travel on stdin, never inline.
    wire_env = {k: v for k, v in env.items()
                if (k.startswith(FORWARD_PREFIXES) or k in settings.env)
                and k != secret.ENV_VAR and k not in stdin_keys}
    inner += f"env {quoted_env_assignments(wire_env)} "
    inner += " ".join(shlex.quote(c) for c in command)
    return " ".join(ssh) + " " + shlex.quote(inner)


def is_local(hostname: str) -> bool:
    # Any 127.0.0.0/8 IP is this machine (lets tests fake an N-host
    # topology on one box: localhost, 127.0.0.1, 127.0.0.2, ...). Parse
    # strictly so a DNS name that merely STARTS with "127." stays remote.
    if hostname in ("localhost", socket.gethostname()):
        return True
    try:
        import ipaddress
        return ipaddress.ip_address(hostname).is_loopback
    except ValueError:
        return False


def routable_local_addr(remote_host: str) -> str:
    """The local address a REMOTE host can reach this machine at (the
    loopback bind host would point remote workers at their own lo). Probes
    the routing table with a connected UDP socket (no packet is sent)."""
    # UDP connect() never sends a packet — it only consults the routing
    # table — so unresolvable/unreachable targets cost nothing. Probe the
    # actual remote first, then any globally-routed address, then DNS.
    for target in (remote_host, "8.8.8.8", "192.0.2.255"):
        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect((target, 9))
                addr = s.getsockname()[0]
            if not addr.startswith("127."):
                return addr
        except OSError:
            continue
    try:
        addr = socket.gethostbyname(socket.gethostname())
        # Debian-style /etc/hosts maps the hostname to 127.0.1.1 — a
        # loopback answer is exactly the wrong thing to advertise.
        if not addr.startswith("127."):
            return addr
    except OSError:
        pass
    return socket.gethostname()


def default_coordinator_addr(assignments: List[HostAssignment],
                             settings: Settings) -> str:
    """Coordinator = process 0's host. All-local job: bind host + a probed
    free port. Mixed local+remote with a local process 0: a *routable*
    local address (remote workers must be able to dial it). Remote process
    0: the hostname + ``Settings.coordinator_port`` (or 29400, the
    conventional JAX coordination-service port) since the launcher cannot
    probe a remote port."""
    host0 = assignments[0].hostname
    if is_local(host0):
        remotes = [a.hostname for a in assignments
                   if not is_local(a.hostname)]
        if not remotes:
            bind = settings.coordinator_bind_host
            port = settings.coordinator_port or find_free_port(bind)
            return f"{bind}:{port}"
        addr = routable_local_addr(remotes[0])
        port = settings.coordinator_port or find_free_port("0.0.0.0")
        return f"{addr}:{port}"
    port = settings.coordinator_port or int(
        os.environ.get("HOROVOD_COORDINATOR_PORT", 29400))
    return f"{host0}:{port}"


def run_host_process(a: HostAssignment, command: Sequence[str],
                     settings: Settings, coordinator_addr: str,
                     secret_key: Optional[bytes], stop: threading.Event,
                     extra_env: Optional[Dict[str, str]] = None,
                     output_dir: Optional[str] = None,
                     sweep_note: Optional[dict] = None) -> int:
    """Run ONE host's worker process to completion; the single launch path
    shared by the static launcher and the elastic driver's generations.

    Any launch-time exception (missing binary, unreachable output dir, ssh
    absent) surfaces as exit code 1, never as a silently dead thread —
    which would read as success while peers hang at rendezvous.
    """
    try:
        env = get_run_env(a, settings, coordinator_addr, secret_key)
        if extra_env:
            env.update(extra_env)
        out = err = None
        opened = []
        if output_dir:
            os.makedirs(output_dir, exist_ok=True)
            out = open(os.path.join(output_dir,
                                    f"rank.{a.process_id}.stdout"), "w")
            err = open(os.path.join(output_dir,
                                    f"rank.{a.process_id}.stderr"), "w")
            opened = [out, err]
        try:
            if is_local(a.hostname):
                return execute(list(command), env=env, stdout=out,
                               stderr=err,
                               prefix=str(a.process_id) if settings.verbose
                               else None,
                               events=[stop], sweep_note=sweep_note)
            line = get_ssh_command(a, command, env, settings,
                                   cwd=os.getcwd(),
                                   secret_on_stdin=secret_key is not None)
            stdin_lines = ([secret.encode(secret_key)]
                           if secret_key is not None else [])
            stdin_lines += stdin_env_lines(env)
            return execute(line, env=dict(os.environ), stdout=out,
                           stderr=err,
                           prefix=str(a.process_id) if settings.verbose
                           else None,
                           events=[stop], sweep_note=sweep_note,
                           stdin_data=("".join(ln + "\n"
                                               for ln in stdin_lines)
                                       .encode()
                                       if stdin_lines else None))
        finally:
            for f in opened:
                f.close()
    except BaseException:
        import traceback
        print(f"[horovod_tpu.runner] failed to launch process "
              f"{a.process_id} on {a.hostname}:", file=sys.stderr)
        traceback.print_exc()
        return 1


def launch_job(assignments: List[HostAssignment], command: Sequence[str],
               settings: Settings, coordinator_addr: Optional[str] = None,
               secret_key: Optional[bytes] = None) -> int:
    """Spawn one worker process per host; first failure tears down the rest
    (reference: gloo_run launch loop + MPI's fate-sharing). Returns the
    first non-zero exit code, else 0."""
    if coordinator_addr is None:
        coordinator_addr = default_coordinator_addr(assignments, settings)
    stop = threading.Event()
    codes: Dict[int, int] = {}
    threads = []

    # --start-timeout bounds STARTUP only (reference semantics): the first
    # worker to exit (success or failure) arms nothing; a worker may run
    # for days. Only `events` (peer failure / launcher shutdown) and an
    # explicit job_timeout_s in Settings.env would bound the lifetime.
    def run_one(a: HostAssignment):
        code = run_host_process(a, command, settings, coordinator_addr,
                                secret_key, stop,
                                output_dir=settings.output_filename)
        codes[a.process_id] = code
        if code != 0:
            stop.set()

    for a in assignments:
        t = threading.Thread(target=run_one, args=(a,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    failures = {pid: c for pid, c in codes.items() if c != 0}
    if failures:
        # Prefer the originating failure (positive exit code) over peers the
        # teardown itself signalled (negative = -signum), so the job reports
        # the real culprit, as the reference's launcher does.
        originating = {p: c for p, c in failures.items() if c > 0}
        pick = originating or failures
        pid = min(pick)
        code = pick[pid]
        print(f"[horovod_tpu.runner] process {pid} exited with code "
              f"{code}; job torn down", file=sys.stderr)
        return code if code > 0 else 128 - code
    return 0
