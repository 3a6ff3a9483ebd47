"""Jit-step deadline monitor: failure containment for the DATA plane.

Reference parity: ``horovod/common/operations.cc`` status propagation —
upstream's collective itself errors when a peer dies (NCCL abort / Gloo
timeout) and the worker raises ``HorovodInternalError``, which
``@hvd.elastic.run`` catches for recovery (SURVEY.md §3.4). XLA's
collectives have no such deadline: a rank blocked inside a *jitted* step
against a dead peer hangs the runtime forever with no error and no signal.
The r5 transport watchdog (core/engine.py ``_bounded``) closed this gap for
ENGINE rounds (host-side numpy collectives) only; this module closes it for
the compiled step itself — the hot path on a real pod.

Mechanism (three layers, see docs/failure_model.md for the full matrix):

- :func:`monitored_call` runs the step dispatch AND the blocking device
  fetch (``jax.block_until_ready`` on the result) on a watcher-visible
  daemon thread while the caller waits in short ticks against a deadline.
  On expiry the caller unblocks: registered engines are marked
  transport-lost (their next op fails fast instead of hanging) and
  ``HorovodInternalError`` is raised — or the process hard-exits with
  ``RESTART_EXIT_CODE`` when configured for runtimes that cannot be
  interrupted (``HOROVOD_STEP_TIMEOUT_ACTION=exit``).
- Per-step heartbeats (:meth:`StepMonitor.heartbeat`) expose steps
  completed and in-flight seconds to any observer (tools/stall.py,
  tests, operators attaching a debugger).
- **Peer-liveness push**: while a step is in flight, a watcher thread
  polls the elastic driver's coordinator service (elastic/service.py
  ``/world`` — the driver's fate-sharing learns of worker exits first and
  publishes them). A "peer died" signal arms an immediate short deadline
  (``HOROVOD_PEER_FAILURE_GRACE_SECONDS``) on the in-flight step, turning
  the ``HOROVOD_STALL_SHUTDOWN_TIME_SECONDS=0`` default from "blocked
  forever" into "rescued within one notification interval".

Deadlines (all env-driven, 0 disables):

- ``HOROVOD_STEP_TIMEOUT_SECONDS`` — absolute ceiling on one monitored
  step (dispatch + device execution + fetch). Default 0: a legitimate
  first step includes XLA compilation, which has no useful global bound.
  The FIRST invocation per step signature (and the first after an
  in-process elastic recovery, which recompiles) gets the ceiling times
  ``HOROVOD_STEP_TIMEOUT_COMPILE_MULTIPLIER`` (default 10) so a
  steady-state-tuned timeout does not spuriously abandon the compile
  step.
- ``HOROVOD_PEER_FAILURE_GRACE_SECONDS`` — how long after a peer-death
  notification the in-flight step may still complete (the surviving
  collective can NEVER complete once a participant is gone; the grace
  only covers delivery/teardown races). Default 5.

With neither deadline armed and no coordinator present,
``monitored_call`` is a direct call — zero threads, zero overhead.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Optional

from . import telemetry as _telemetry
from .exceptions import HorovodInternalError
from .logging import get_logger

#: env: absolute per-step deadline in seconds (0 = disabled).
STEP_TIMEOUT_ENV = "HOROVOD_STEP_TIMEOUT_SECONDS"

#: env: grace window after a peer-death notification (0 = disabled).
PEER_GRACE_ENV = "HOROVOD_PEER_FAILURE_GRACE_SECONDS"

#: env: step-timeout scale for the first invocation per step signature —
#: that call includes XLA compilation, which a steady-state timeout must
#: not count against the step deadline.
COMPILE_MULT_ENV = "HOROVOD_STEP_TIMEOUT_COMPILE_MULTIPLIER"

#: env: "raise" (default) raises HorovodInternalError in the blocked
#: caller; "exit" hard-exits with RESTART_EXIT_CODE for runtimes where a
#: Python exception cannot unwind (the fetch thread owns no GIL-visible
#: frame to interrupt — raising only works because the CALLER waits in
#: Python; when the caller itself sits inside an uninterruptible C
#: extension, exit is the only rescue that reaches the driver).
ACTION_ENV = "HOROVOD_STEP_TIMEOUT_ACTION"

DEFAULT_PEER_GRACE_S = 5.0
DEFAULT_COMPILE_MULT = 10.0

#: watcher/caller tick, seconds. Short enough that a peer-death rescue is
#: dominated by the notification interval, not the tick.
_TICK_S = 0.25


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _is_runtime_error(exc: BaseException) -> bool:
    """True for XLA/collective runtime failures — the class of error a dead
    or disconnected peer produces (gloo connection reset, XLA runtime
    abort). These are the reference's recoverable collective errors, so a
    monitored step translates them into ``HorovodInternalError`` for
    ``@elastic.run``. Matched by name: the concrete exception type moved
    across jax versions (xla_extension.XlaRuntimeError →
    jax.errors.JaxRuntimeError)."""
    for klass in type(exc).__mro__:
        if klass.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
    return False


class StepMonitor:
    """Process-wide monitor for compiled train steps (one per process —
    use the module-level :func:`monitor` accessor)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._steps_completed = 0
        self._inflight_since: Optional[float] = None
        self._inflight_what: Optional[str] = None
        # Peer death: (monotonic time observed, description).
        self._peer_failure: Optional[tuple] = None
        # Graceful membership bump observed mid-round: (monotonic time,
        # description). Peers exit RESTART at their next commit, so an
        # in-flight round they leave behind can never complete.
        self._membership_reset: Optional[tuple] = None
        # Control-plane loss: the coordinator has been continuously
        # unreachable past HOROVOD_COORDINATOR_LOST_TIMEOUT_SECONDS
        # (CoordinatorLostError from the retrying client). Unlike a peer
        # failure there is no grace: by construction the loss window
        # already elapsed before this is set.
        self._control_plane_lost: Optional[str] = None
        # Last coordinator failure_seq observed. The seq is monotonic
        # across generations, so a relaunched survivor's first poll can
        # see a nonzero count inherited from its predecessors' deaths —
        # the watcher arms only when the (generation-scoped) failure
        # list is non-empty, and otherwise just baselines the seq.
        self._failure_seq_seen = 0
        # Completions per step signature: the first invocation of a
        # signature includes XLA compilation and gets the compile
        # multiplier on its deadline.
        self._completed_by_what: Dict[str, int] = {}
        self._engines: list = []   # weakrefs of registered engines
        self._engine_waits = 0     # engine rounds currently blocked
        self._queue = None         # fetch-thread work queue, lazy
        self._watcher_started = False
        self._client = None        # CoordinatorClient, lazy
        self._client_missing = False
        self._telemetry_pushed = 0.0  # last piggybacked metrics push

    # -- configuration (re-read per step: tests and drivers set env late) --

    @property
    def step_timeout_s(self) -> float:
        return _env_float(STEP_TIMEOUT_ENV, 0.0)

    @property
    def peer_grace_s(self) -> float:
        return _env_float(PEER_GRACE_ENV, DEFAULT_PEER_GRACE_S)

    @property
    def compile_mult(self) -> float:
        return max(_env_float(COMPILE_MULT_ENV, DEFAULT_COMPILE_MULT), 1.0)

    @property
    def action(self) -> str:
        return os.environ.get(ACTION_ENV, "raise").lower()

    # -- engine registration ------------------------------------------------

    def register_engine(self, engine: Any) -> None:
        """Engines register so a step-deadline expiry can mark them
        transport-lost (their blocking transport shares the fate of the
        dead collective — letting the NEXT engine op hang would just move
        the hang)."""
        import weakref
        with self._lock:
            self._engines = [r for r in self._engines if r() is not None]
            if not any(r() is engine for r in self._engines):
                self._engines.append(weakref.ref(engine))

    def _mark_engines_lost(self, reason: str) -> None:
        with self._lock:
            refs = list(self._engines)
        for r in refs:
            eng = r()
            if eng is not None:
                try:
                    eng._transport_lost = reason
                except Exception:   # noqa: BLE001 — best effort
                    pass

    # -- heartbeats ---------------------------------------------------------

    def heartbeat(self) -> Dict[str, Any]:
        """Watcher-visible step progress snapshot.

        The snapshot is also published through the telemetry registry
        (``hvd_heartbeat_*`` gauges + ``hvd_sentinel_*``), so the
        torch/TF heartbeat spans and jit-step spans report through one
        surface instead of a parallel bespoke dict."""
        from . import sentinel as _sentinel
        with self._lock:
            now = time.monotonic()
            hb = {
                "steps_completed": self._steps_completed,
                "in_flight": self._inflight_since is not None,
                "in_flight_what": self._inflight_what,
                "in_flight_seconds": (now - self._inflight_since
                                      if self._inflight_since is not None
                                      else 0.0),
                "peer_failure": (self._peer_failure[1]
                                 if self._peer_failure else None),
                "control_plane_lost": self._control_plane_lost,
                # Numeric-integrity counters (core/sentinel.py): zeros
                # when no sentinel is active this process.
                "sentinel": _sentinel.counters(),
            }
        _telemetry.set_gauge("hvd_heartbeat_steps_completed",
                             hb["steps_completed"])
        _telemetry.set_gauge("hvd_heartbeat_in_flight",
                             1.0 if hb["in_flight"] else 0.0)
        _telemetry.set_gauge("hvd_heartbeat_in_flight_seconds",
                             hb["in_flight_seconds"])
        for k, v in hb["sentinel"].items():
            _telemetry.set_gauge("hvd_sentinel_%s" % k, float(v))
        return hb

    # -- peer liveness ------------------------------------------------------

    def notify_peer_failure(self, info: str) -> None:
        """Arm the peer-death deadline on the in-flight step (called by the
        coordinator watcher; tests inject directly)."""
        first = False
        with self._lock:
            if self._peer_failure is None:
                self._peer_failure = (time.monotonic(), info)
                first = True
        if first:
            # The survivor's "rescue" record: a peer died and this rank
            # armed containment. Dump the ring now — even if the ensuing
            # restart goes through os._exit (which skips atexit), the
            # forensic record of the last steps already exists on disk.
            _telemetry.inc("hvd_peer_failures_total")
            _telemetry.record_event("rescue", reason=info,
                                    grace_s=self.peer_grace_s)
            _telemetry.dump_flight("peer_failure")
        get_logger().warning(
            "peer failure notified: %s — arming %.1fs grace deadline on "
            "the in-flight step (%s)", info, self.peer_grace_s,
            PEER_GRACE_ENV)

    def notify_membership_reset(self, info: str) -> None:
        """Arm the abandon deadline for a GRACEFUL membership bump observed
        while a round is in flight. The cooperative reset protocol assumes
        every worker polls the bump at its next commit — but a worker whose
        jittered commit-time poll was paced past the bump can already be
        parked inside the next collective when its peers restart-exit; no
        commit ever comes, and the generation wedges until the stall window
        (the host-add test deadlocked exactly so: the resetter blocked in
        the runtime's shutdown barrier against the wedged survivor). The
        peer-grace window gives an in-flight round that CAN still complete
        (peers not yet exited) time to finish and take the interrupt at
        commit instead; abandoning early costs nothing extra — a version
        bump means this worker must roll back to its last commit and
        restart either way."""
        first = False
        with self._lock:
            if self._membership_reset is None:
                self._membership_reset = (time.monotonic(), info)
                first = True
        if first:
            _telemetry.record_event("generation_change", reason=info,
                                    grace_s=self.peer_grace_s)
            get_logger().info(
                "membership changed mid-round: %s — arming %.1fs grace "
                "deadline on the in-flight round (%s)", info,
                self.peer_grace_s, PEER_GRACE_ENV)

    def notify_control_plane_lost(self, info: str) -> None:
        """Mark the control plane lost (called when the retrying client
        raises CoordinatorLostError — the continuous-failure window has
        already elapsed, so any in-flight step is abandoned on the next
        deadline tick: with the driver gone, nobody will relaunch a
        generation that wedges later, and nobody is publishing peer
        deaths anymore — the push layer is blind)."""
        first = False
        with self._lock:
            if self._control_plane_lost is None:
                self._control_plane_lost = info
                first = True
        if first:
            _telemetry.inc("hvd_control_plane_lost_total")
            _telemetry.record_event("rpc_escalation", reason=info)
            get_logger().error("control plane lost: %s — escalating "
                               "instead of polling a dead coordinator "
                               "forever", info)

    def clear_peer_failure(self) -> None:
        with self._lock:
            self._peer_failure = None

    def reset_for_recovery(self) -> None:
        """Called by elastic/run_fn.py after an IN-PROCESS re-init: the
        peer-failure flag is scoped to the OLD world — left armed, its
        long-expired grace deadline would abandon every step of the
        recovered run on the first tick. The per-signature completion
        counts are dropped too: the recovered world recompiles, so the
        next step of each signature earns the compile multiplier again.
        (A process RESTART needs none of this — the new process gets a
        fresh monitor.)"""
        with self._lock:
            self._peer_failure = None
            self._membership_reset = None
            self._control_plane_lost = None
            self._completed_by_what = {}
            # Re-resolve the coordinator on next use: the recovery may
            # have come with a new driver/address in the environment.
            self._client = None
            self._client_missing = False

    def peer_watch_available(self) -> bool:
        """A coordinator to poll exists (we run under the elastic driver)
        and the grace deadline is not disabled."""
        if self.peer_grace_s <= 0:
            return False
        from ..elastic import constants as C
        return bool(os.environ.get(C.COORD_ADDR_ENV))

    def _coordinator_client(self):
        if self._client is not None or self._client_missing:
            return self._client
        from ..elastic import constants as C
        from ..runner import secret as _secret
        addr = os.environ.get(C.COORD_ADDR_ENV)
        key_s = os.environ.get(_secret.ENV_VAR)
        if not addr or not key_s:
            self._client_missing = True
            return None
        from ..elastic.service import CoordinatorClient
        self._client = CoordinatorClient(addr, _secret.decode(key_s))
        return self._client

    def _poll_interval_s(self) -> float:
        from ..elastic import constants as C
        return _env_float(C.POLL_INTERVAL_ENV, C.DEFAULT_POLL_INTERVAL_S)

    def _long_poll_s(self) -> float:
        from ..elastic import constants as C
        return _env_float(C.LONG_POLL_ENV, C.DEFAULT_LONG_POLL_S)

    def _ensure_watcher(self) -> None:
        """Background poller of the driver's ``/world`` failure feed. Only
        polls while a step is in flight — an idle process costs the
        coordinator nothing."""
        with self._lock:
            if self._watcher_started:
                return
            self._watcher_started = True
        threading.Thread(target=self._watch_loop, daemon=True,
                         name="hvd-step-watcher").start()

    def begin_engine_wait(self) -> None:
        """Engine ``_bounded`` wait-loop entry: keeps the failure-feed
        watcher polling while a host-side round (not a jitted step) is the
        thing blocked against a dead peer."""
        with self._lock:
            self._engine_waits += 1

    def end_engine_wait(self) -> None:
        with self._lock:
            self._engine_waits -= 1

    def _watch_loop(self) -> None:
        while True:
            time.sleep(max(self._poll_interval_s(), 0.05))
            with self._lock:
                inflight = (self._inflight_since is not None
                            or self._engine_waits > 0)
                have_failure = self._peer_failure is not None
            if not inflight or have_failure:
                continue
            client = self._coordinator_client()
            if client is None:
                continue
            from ..elastic.service import CoordinatorLostError
            try:
                # Bounded long-poll once the client holds a world cursor:
                # the request parks server-side until the membership/
                # failure counters move, so a peer death reaches this
                # watcher IMMEDIATELY (the rescue deadline arms on push
                # latency, not poll cadence) while an unchanged world
                # costs one tiny not-modified reply per bound instead of
                # one full payload per tick.
                wait = self._long_poll_s()
                world = client.get_world(wait=wait if wait > 0 else None)
            except CoordinatorLostError as e:
                # Escalate via the deadline machinery: the in-flight
                # step/round is abandoned on its next tick.
                self.notify_control_plane_lost(str(e))
                continue
            self._maybe_push_telemetry(client)
            if not world:
                continue
            self._maybe_notify_membership_reset(world)
            seq = int(world.get("failure_seq", 0))
            prev = self._failure_seq_seen
            # Always adopt the coordinator's seq — including DOWN (a new
            # coordinator after a full driver restart starts from 0).
            self._failure_seq_seen = seq
            if seq <= prev:
                continue
            failures = world.get("failures") or []
            if not failures:
                # Seq moved but the generation-scoped failure list is
                # empty: the deaths predate this generation's
                # update_world (a relaunched survivor inheriting its
                # predecessors' monotonic count) — nothing in OUR world
                # died; baseline without arming. A death in our OWN
                # generation always rides a non-empty list, even on the
                # very first poll.
                continue
            desc = ", ".join(
                f"{f.get('host')}(exit {f.get('code')})"
                for f in failures)
            self.notify_peer_failure(desc)

    def _maybe_notify_membership_reset(self, world: Dict[str, Any]) -> None:
        """Arm the graceful-reset deadline when the coordinator's membership
        version has moved past the version this worker was launched with
        (see notify_membership_reset for why commit-time polling alone is
        not enough)."""
        with self._lock:
            if self._membership_reset is not None:
                return
        from ..elastic import constants as C
        try:
            launch = int(os.environ.get(C.WORLD_VERSION_ENV) or 0)
            version = int(world.get("version") or 0)
        except (TypeError, ValueError):
            return
        if launch and version > launch:
            self.notify_membership_reset(
                f"membership version {version} > launch version {launch}")

    def _maybe_push_telemetry(self, client) -> None:
        """Piggyback a compact metrics delta (plus a throttled heartbeat
        ring event) on the ``/world`` poll the watcher already pays for —
        no extra poll loop, no extra connection."""
        now = time.monotonic()
        if now - self._telemetry_pushed < 2.0:
            return
        self._telemetry_pushed = now
        hb = self.heartbeat()
        _telemetry.record_event(
            "heartbeat", steps_completed=hb["steps_completed"],
            in_flight=hb["in_flight"], in_flight_what=hb["in_flight_what"],
            in_flight_seconds=round(hb["in_flight_seconds"], 3))
        delta = _telemetry.export_delta()
        if delta is None:
            return
        try:
            client.push_metrics(_telemetry.active().rank, delta)
        except Exception as e:   # noqa: BLE001 — push is best-effort;
            # escalation belongs to the get_world path, not the piggyback.
            get_logger().debug("telemetry push skipped: %s", e)

    # -- deadline evaluation ------------------------------------------------

    def deadline_reason(self, started: float,
                        timeout_scale: float = 1.0) -> Optional[str]:
        """Why the in-flight step (started at monotonic ``started``) must
        be abandoned now — or None. Shared with the engine's ``_bounded``
        wait loop so peer-liveness rescues engine rounds too.
        ``timeout_scale`` widens the step ceiling for first-per-signature
        calls that include XLA compilation."""
        now = time.monotonic()
        timeout = self.step_timeout_s * timeout_scale
        if timeout > 0 and now - started >= timeout:
            scaled = (f" x{timeout_scale:.0f} compile allowance "
                      f"({COMPILE_MULT_ENV})" if timeout_scale != 1.0
                      else "")
            return (f"step exceeded {STEP_TIMEOUT_ENV}="
                    f"{self.step_timeout_s:.0f}s{scaled}")
        with self._lock:
            pf = self._peer_failure
            mr = self._membership_reset
            cpl = self._control_plane_lost
        if pf is not None and now - pf[0] >= self.peer_grace_s:
            return (f"peer died ({pf[1]}); in-flight collective cannot "
                    f"complete ({PEER_GRACE_ENV}={self.peer_grace_s:.0f}s "
                    "elapsed)")
        if mr is not None and now - mr[0] >= self.peer_grace_s:
            return (f"hosts updated ({mr[1]}); peers reset at their next "
                    "commit, so the in-flight round cannot complete — "
                    "restarting into the new world "
                    f"({PEER_GRACE_ENV}={self.peer_grace_s:.0f}s elapsed)")
        if cpl is not None:
            # No grace on top: the continuous-failure window already
            # elapsed inside the client before this flag was set.
            return f"control plane lost ({cpl})"
        return None

    def armed(self) -> bool:
        if self.step_timeout_s > 0:
            return True
        with self._lock:
            if self._peer_failure is not None and self.peer_grace_s > 0:
                return True
            if self._membership_reset is not None and self.peer_grace_s > 0:
                return True
            if self._control_plane_lost is not None:
                return True
        return self.peer_watch_available()

    # -- heartbeat-only spans (torch/TF step paths) --------------------------

    def step_span(self, what: str = "step"):
        """Heartbeat window WITHOUT moving the call to the fetch thread —
        for step paths whose blocking happens inside engine rounds (torch
        ``optimizer.step``/TF ``tape.gradient``): the engine's ``_bounded``
        delivers the deadline rescue there; this span keeps the heartbeat
        honest and gives the peer-liveness watcher an in-flight window to
        poll under. (Moving TF's tracing to another thread would serialize
        on its tracing lock — see the thread-sim trap in CLAUDE.md.)"""
        import contextlib

        @contextlib.contextmanager
        def span():
            with self._lock:
                started = self._inflight_since = time.monotonic()
                self._inflight_what = what
            _telemetry.record_event("step_begin", what=what)
            if self.peer_watch_available():
                self._ensure_watcher()
            try:
                yield
                with self._lock:
                    self._steps_completed += 1
                self._note_step_done(what, started)
            finally:
                with self._lock:
                    self._inflight_since = None
                    self._inflight_what = None
        return span()

    # -- the monitored call -------------------------------------------------

    def _note_step_done(self, what: str, started: Optional[float]) -> None:
        """Per-step telemetry: counters/histogram plus a ring event. All
        inputs are host scalars the monitor already holds — never a
        device fetch (lint-blocking-telemetry guards this invariant)."""
        end = time.monotonic()
        dt = (end - started) if started is not None else 0.0
        with self._lock:
            n = self._steps_completed
        _telemetry.inc("hvd_steps_total", what=what)
        _telemetry.observe("hvd_step_seconds", dt, what=what)
        _telemetry.set_gauge("hvd_last_step", n)
        _telemetry.set_gauge("hvd_step_wall_seconds", dt, what=what)
        _telemetry.record_event("step_end", what=what, step=n,
                                seconds=round(dt, 6))

    def _fetch_worker(self, q) -> None:
        """Fetch-thread loop. DAEMON on purpose: after a deadline expiry it
        stays parked in the dead collective forever; a non-daemon thread
        there would hang interpreter shutdown and the
        ``sys.exit(RESTART_EXIT_CODE)`` escape in elastic/run_fn.py must
        actually exit (same design as the engine's round thread). The
        worker owns ``q`` (never reads ``self._queue`` for work): after a
        SPURIOUS expiry (the step completes late) it must exit instead of
        racing the replacement worker for the new queue's items."""
        while self._queue is q:
            fn, box = q.get()
            try:
                box["result"] = fn()
            except BaseException as e:   # noqa: BLE001 — relayed to caller
                box["error"] = e
            box["done"].set()

    def _fail(self, reason: str):
        msg = (f"monitored step abandoned: {reason}; the data-plane "
               "transport is considered lost — re-init required (under "
               "hvdrun --min-np the elastic driver relaunches the job)")
        with self._lock:
            # The fetch thread is parked in the dead collective forever;
            # orphan it (daemon) so an IN-PROCESS recovery (standalone
            # elastic mode) gets a fresh worker instead of queueing new
            # steps behind the wedged one.
            self._queue = None
        self._mark_engines_lost(msg)
        get_logger().error("%s", msg)
        _telemetry.inc("hvd_watchdog_expiries_total")
        _telemetry.record_event("watchdog_expiry", reason=reason)
        # Dump BEFORE the exit below: os._exit skips atexit hooks, so
        # this is the only chance to leave a flight record.
        _telemetry.dump_flight("watchdog_expiry")
        if self.action == "exit":
            from ..elastic import constants as C
            # The runtime cannot be interrupted from Python: make the
            # driver's fate-sharing see a dead process instead of a
            # silent hang. os._exit skips atexit hooks that would block
            # on the wedged runtime.
            os._exit(C.RESTART_EXIT_CODE)
        raise HorovodInternalError(msg)

    def monitored_call(self, fn: Callable[[], Any],
                       what: str = "train_step") -> Any:
        """Run ``fn`` (the step dispatch) and block until its result's
        device buffers are ready, under the step/peer deadlines. Unarmed:
        a direct call with only heartbeat accounting."""
        import jax
        begun = time.monotonic()
        with self._lock:
            self._inflight_since = begun
            self._inflight_what = what
            # First call per signature = compilation included: widen the
            # step ceiling so a steady-state-tuned timeout does not
            # abandon the compile step (recompiles after an elastic
            # resize re-earn this via reset_for_recovery).
            first_of_signature = self._completed_by_what.get(what, 0) == 0
        scale = self.compile_mult if first_of_signature else 1.0
        _telemetry.record_event("step_begin", what=what)
        try:
            if not self.armed():
                out = fn()
                with self._lock:
                    started = self._inflight_since
                    self._steps_completed += 1
                    self._completed_by_what[what] = \
                        self._completed_by_what.get(what, 0) + 1
                self._note_step_done(what, started)
                return out
            if self.peer_watch_available():
                self._ensure_watcher()
            if self._queue is None:
                import queue
                q = self._queue = queue.Queue()
                threading.Thread(target=self._fetch_worker, args=(q,),
                                 daemon=True, name="hvd-step-fetch").start()

            def run_and_fetch():
                return jax.block_until_ready(fn())

            box = {"done": threading.Event()}
            started = self._inflight_since
            self._queue.put((run_and_fetch, box))
            while True:
                if box["done"].wait(timeout=_TICK_S):
                    if "error" in box:
                        err = box["error"]
                        if _is_runtime_error(err):
                            # A dead peer that ERRORS the collective
                            # (connection reset) instead of hanging it is
                            # the same failure — same recovery path.
                            raise HorovodInternalError(
                                f"collective runtime error inside "
                                f"monitored {what}: {err}") from err
                        raise err
                    with self._lock:
                        self._steps_completed += 1
                        self._completed_by_what[what] = \
                            self._completed_by_what.get(what, 0) + 1
                    self._note_step_done(what, started)
                    return box["result"]
                reason = self.deadline_reason(started, timeout_scale=scale)
                if reason is not None:
                    return self._fail(reason)
        finally:
            with self._lock:
                self._inflight_since = None
                self._inflight_what = None


_monitor: Optional[StepMonitor] = None
_monitor_lock = threading.Lock()


def monitor() -> StepMonitor:
    """The process-wide StepMonitor."""
    global _monitor
    if _monitor is None:
        with _monitor_lock:
            if _monitor is None:
                _monitor = StepMonitor()
    return _monitor


def monitored_step(fn: Callable, what: str = "train_step") -> Callable:
    """Wrap a step callable so every invocation runs under the monitor
    (train.make_train_step and the torch/TF step paths use this). The
    wrapped step returns FULLY-REALIZED results (the device fetch happens
    on the monitored thread), so callers need no extra
    ``block_until_ready``. Attributes like ``.lower`` pass through for AOT
    introspection."""
    def wrapped(*args, **kwargs):
        return monitor().monitored_call(lambda: fn(*args, **kwargs),
                                        what=what)
    for attr in ("lower", "chosen", "lower_probe", "lower_apply",
                 "lower_skip", "sentinel"):
        if hasattr(fn, attr):
            setattr(wrapped, attr, getattr(fn, attr))
    return wrapped


def engine_deadline_reason(started: float) -> Optional[str]:
    """Hook for core/engine.py ``_bounded``: the peer-death/step deadlines
    also bound engine rounds (a host-side collective against a dead peer
    is the same hang). Cheap when unarmed."""
    m = _monitor
    if m is None:
        return None
    return m.deadline_reason(started)


def engine_peer_watch_armed() -> bool:
    """True when engine rounds must route through their round thread even
    with the stall windows unset — the peer-liveness push needs a waiting
    caller to deliver the rescue to."""
    m = monitor()
    if not m.peer_watch_available():
        return False
    m._ensure_watcher()
    return True
