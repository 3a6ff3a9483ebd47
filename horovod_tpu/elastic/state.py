"""Elastic state objects: commit / restore / sync.

Reference parity (SURVEY.md §3.4, §5.3/§5.4): ``horovod/common/elastic.py``
(``State``, ``ObjectState``) and ``horovod/torch/elastic/state.py``
(``TorchState``). Semantics preserved:

- ``commit()`` — snapshot the state (the in-memory checkpoint the training
  loop rolls back to after a failure) and check for host updates.
- ``restore()`` — roll back to the last commit (after
  ``HorovodInternalError``).
- ``sync()`` — make every worker identical to rank 0 (after membership
  change, when no rollback is needed).
- reset callbacks — user hooks run after a re-initialisation (the reference
  uses these to rebuild samplers/optimizers for the new world size).

TPU deltas:

- Snapshots are **host copies** (``jax.device_get``) of array pytrees:
  device buffers die with the mesh on reset, host snapshots do not.
- When ``HOROVOD_ELASTIC_COMMIT_DIR`` is set (the elastic driver always
  sets it), ``commit()`` also persists the snapshot to disk atomically —
  on EVERY process, each to its own local disk, so losing any host (even
  the one that was process 0) leaves survivors a restore point; restores
  pick the newest commit across the relaunched world. This is what makes
  **process-restart elasticity** (the TPU-true mode — see
  elastic/run_fn.py) lossless: a relaunched generation restores the latest
  commit instead of starting over. The reference keeps commits purely
  in-memory because its workers survive resets; ours may not.
- ``JaxState`` is the ``TorchState`` analog holding ``params``/``opt_state``
  pytrees plus arbitrary scalar attrs (epoch, batch, ...).
- Commits are **pipelined and content-addressed** (PR 9): ``commit()``
  takes a cheap on-device copy and returns; a double-buffered background
  writer (:class:`_CommitWriter`) overlaps the device→host transfer and
  serialization with subsequent steps, stores each pytree leaf as a
  blake2b-addressed blob (``checkpoint/store.py`` :class:`BlobStore` —
  unchanged leaves dedup across commits and across ranks sharing the
  directory), and publishes one small manifest atomically LAST. The step
  loop only ever blocks on BACK-PRESSURE — the previous commit still in
  flight (``hvd_commit_stall_seconds``). ``HOROVOD_COMMIT_ASYNC=0``
  restores the inline write. Legacy single-frame commits
  (``state.latest.pkl``/``state.prev.pkl``) still restore; the
  newest→oldest fallback walk now spans manifests first, then frames
  (docs/checkpointing.md).
"""

from __future__ import annotations

import copy
import hashlib
import hmac
import os
import pickle
import random
import tempfile
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import jax

from ..core import telemetry as _telemetry
from ..core.exceptions import HostsUpdatedInterrupt
from ..core.logging import get_logger
from . import constants as C


class WorkerNotificationManager:
    """Commit-time membership watcher (worker side).

    Reference parity: ``horovod/runner/elastic/worker.py``'s
    WorkerNotificationManager, with the push inverted into a rate-limited
    poll of the driver's coordinator service (see elastic/service.py).

    Pod-scale cadence (benchmarks/control_plane.py): SPMD commits happen
    in lockstep (collectives synchronize the steps), so N workers whose
    rate-limiters all expire together poll the coordinator on aligned
    ticks — a thundering herd every interval. The gap to the next allowed
    poll is therefore drawn per-worker as ``interval * uniform(1-j, 1+j)``
    (``HOROVOD_ELASTIC_POLL_JITTER``, decorrelated: each gap independent),
    and the interval itself stretches to the server-advertised ``poll_s``
    pacing so aggregate request rate stays ~flat as the world grows. The
    FIRST poll of a generation stays immediate — a membership bump that
    predates the launch must be observed at the first commit, not an
    interval later. ``_clock``/``_rng`` are injectable (fake-clock tests).
    """

    def __init__(self):
        self._client = None
        self._launch_version: Optional[int] = None
        self._next_poll_due = 0.0    # 0 = first check() polls immediately
        self._poll_interval_s = C.DEFAULT_POLL_INTERVAL_S
        self._jitter = C.DEFAULT_POLL_JITTER
        self._pending = False
        self._lock = threading.Lock()
        self._clock: Callable[[], float] = time.monotonic
        self._rng = random.Random()

    def init_from_env(self) -> None:
        addr = os.environ.get(C.COORD_ADDR_ENV)
        if not addr or self._client is not None:
            return
        from ..runner import secret as _secret
        key_s = os.environ.get(_secret.ENV_VAR)
        if not key_s:
            return
        from .service import CoordinatorClient
        self._client = CoordinatorClient(addr, _secret.decode(key_s))
        v = os.environ.get(C.WORLD_VERSION_ENV)
        self._launch_version = int(v) if v else None
        iv = os.environ.get(C.POLL_INTERVAL_ENV)
        if iv:
            try:
                # The driver pins this to its discovery cadence so a short
                # generation (few commits) still observes a mid-run bump.
                self._poll_interval_s = float(iv)
            except ValueError:
                pass
        jv = os.environ.get(C.POLL_JITTER_ENV)
        if jv:
            try:
                self._jitter = max(0.0, float(jv))
            except ValueError:
                pass

    def _schedule_next_poll(self, now: float) -> None:
        """Earliest next poll: the configured interval stretched to the
        server's advertised pacing, jittered so lockstep workers drift
        apart instead of herding on aligned ticks. Caller holds the lock."""
        interval = self._poll_interval_s
        adv = getattr(self._client, "advertised_poll_s", None)
        if adv:
            interval = max(interval, float(adv))
        if self._jitter > 0:
            gap = interval * self._rng.uniform(1.0 - self._jitter,
                                               1.0 + self._jitter)
        else:
            gap = interval
        self._next_poll_due = now + max(gap, 0.0)

    def check(self) -> None:
        """Raise HostsUpdatedInterrupt if membership moved past the version
        this worker generation was launched with."""
        with self._lock:
            if self._pending:
                self._pending = False
                raise HostsUpdatedInterrupt()
            if self._client is None or self._launch_version is None:
                return
            now = self._clock()
            if now < self._next_poll_due:
                return
            self._schedule_next_poll(now)
            from ..core.exceptions import HorovodInternalError
            from .service import CoordinatorLostError
            try:
                world = self._client.get_world()
            except CoordinatorLostError as e:
                # Persistent control-plane loss (the retrying client's
                # continuous-failure window elapsed): escalate instead of
                # treating a dead driver as "no change" forever. The step
                # monitor is marked first so heartbeats/observers see WHY,
                # then HorovodInternalError unwinds to @elastic.run —
                # restart-exit under a (possibly restarted) driver, or an
                # in-process reset attempt standalone.
                get_logger().error("%s", e)
                from ..core.watchdog import monitor
                monitor().notify_control_plane_lost(str(e))
                raise HorovodInternalError(str(e)) from e
            # Piggyback the compact metrics delta on the poll this commit
            # already paid for — the coordinator aggregates it for
            # GET /metrics. Best-effort: cumulative values mean a dropped
            # push is healed by the next one.
            delta = _telemetry.export_delta()
            if delta is not None:
                try:
                    self._client.push_metrics(_telemetry.active().rank,
                                              delta)
                except Exception as push_err:  # noqa: BLE001
                    get_logger().debug("telemetry push skipped: %s",
                                       push_err)
            if world is not None and world["version"] > self._launch_version:
                get_logger().info(
                    "membership version %d > launch version %d: hosts updated",
                    world["version"], self._launch_version)
                # Don't re-raise forever on subsequent checks: the interrupt
                # fires once per observed change.
                self._launch_version = world["version"]
                raise HostsUpdatedInterrupt()

    def signal(self) -> None:
        """Inject a host-update (tests / in-process driver)."""
        with self._lock:
            self._pending = True

    def register(self) -> bool:
        """Announce this worker to the driver (reference:
        registration.py last-seen bookkeeping; feeds the driver's
        ``registered_workers`` observability view). The client retries
        under the RPC backoff policy; a False return is logged here AND
        surfaces driver-side when the start-timeout trips (the driver
        names workers that never registered)."""
        with self._lock:
            if self._client is None:
                return True
            pid = os.environ.get("HOROVOD_PROCESS_ID")
            if pid is None:
                return True
            ok = self._client.register(int(pid))
        if not ok:
            get_logger().warning(
                "worker registration with the coordinator failed after "
                "retries (process_id=%s) — the driver will log this "
                "worker as never-registered at its start-timeout", pid)
        return ok


notification_manager = WorkerNotificationManager()


class State:
    """Base state machinery (reference: common/elastic.py State)."""

    def __init__(self):
        self._reset_callbacks: List[Callable[[], None]] = []

    def register_reset_callbacks(self,
                                 callbacks: List[Callable[[], None]]) -> None:
        self._reset_callbacks.extend(callbacks)

    def on_reset(self) -> None:
        self.reset()
        for cb in self._reset_callbacks:
            cb()

    def reset(self) -> None:
        """Override: rebuild world-size-dependent members."""

    def commit(self) -> None:
        self.save()
        self.check_host_updates()

    def check_host_updates(self) -> None:
        # Preemption check first, and before the rate-limited poll: the
        # lifecycle flag is a local attribute read (no RPC), and commit()
        # already ran save() — so the commit that carried us to this seam
        # IS the out-of-cadence commit the preemption grace window buys.
        from ..core import lifecycle as _lifecycle
        if _lifecycle.preempt_requested():
            from ..core.exceptions import PreemptionInterrupt
            raise PreemptionInterrupt(_lifecycle.preempt_signum())
        notification_manager.init_from_env()
        notification_manager.check()

    def save(self) -> None:
        raise NotImplementedError

    def restore(self) -> None:
        raise NotImplementedError

    def sync(self) -> None:
        raise NotImplementedError


def _commit_path(commit_dir: str) -> str:
    return os.path.join(commit_dir, "state.latest.pkl")


def _prev_commit_path(commit_dir: str) -> str:
    return os.path.join(commit_dir, "state.prev.pkl")


#: Commit-integrity trailer: <pickle body><16-byte blake2b digest><magic>.
#: The magic goes LAST so a truncation — the dominant real-world corruption
#: (full disk, killed writer, chopped copy) — always destroys it and the
#: file is recognizably damaged rather than mis-verified.
_CHECK_MAGIC = b"HVDCK1\n"
_CHECK_DIGEST_SIZE = 16


def _frame(body: bytes) -> bytes:
    digest = hashlib.blake2b(body, digest_size=_CHECK_DIGEST_SIZE).digest()
    return body + digest + _CHECK_MAGIC


def _unframe(blob: bytes) -> Optional[bytes]:
    """Verified pickle body, or None when the checksum fails. Files without
    the trailer (pre-integrity commits) are accepted as-is — their only
    protection is pickle's own parse errors, exactly the legacy behavior."""
    if not blob.endswith(_CHECK_MAGIC):
        return blob
    body = blob[:-(len(_CHECK_MAGIC) + _CHECK_DIGEST_SIZE)]
    digest = blob[len(body):-len(_CHECK_MAGIC)]
    want = hashlib.blake2b(body, digest_size=_CHECK_DIGEST_SIZE).digest()
    return body if hmac.compare_digest(digest, want) else None


def _persist(commit_dir: str, payload: Dict[str, Any]) -> None:
    """Atomic write (tmp + rename) so a crash mid-commit never corrupts the
    restore point, with a checksum trailer and one-deep rotation: the
    previous committed generation survives as ``state.prev.pkl`` so
    ``load_persisted`` can fall back when the newest commit fails
    verification (docs/failure_model.md — corruption containment).

    EVERY process persists to its own local disk (the commit_dir path is
    per-host), so losing any host — including the one that was process 0 —
    leaves survivors with a usable restore point; ``load_persisted_world``
    picks the newest across the relaunched world.
    """
    os.makedirs(commit_dir, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=commit_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(_frame(pickle.dumps(payload)))
        latest = _commit_path(commit_dir)
        if os.path.exists(latest):
            # Rotate BEFORE replacing: latest is still intact here, so the
            # fallback is always a fully-written commit.
            os.replace(latest, _prev_commit_path(commit_dir))
        os.replace(tmp, latest)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _load_verified(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path, "rb") as f:
            blob = f.read()
        body = _unframe(blob)
        if body is None:
            get_logger().error(
                "commit %s failed checksum verification — ignoring it",
                path)
            return None
        return pickle.loads(body)
    except (OSError, pickle.UnpicklingError, EOFError):
        return None


# ---------------------------------------------------------------------------
# Content-addressed commits: per-leaf blobs + manifest (checkpoint/store.py)
# ---------------------------------------------------------------------------

_CAS_SUBDIR = "cas"


class _LeafRef:
    """Placeholder leaf inside a pickled pytree *skeleton*: an index into
    the manifest's leaf-blob list. Pickling the skeleton (the original
    containers with ``_LeafRef`` leaves) instead of a ``PyTreeDef`` keeps
    the on-disk format independent of jax's treedef pickling."""

    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index

    def __getstate__(self):
        return self.index

    def __setstate__(self, state):
        self.index = state


def _cas_store(commit_dir: str):
    from ..checkpoint.store import BlobStore
    return BlobStore(os.path.join(commit_dir, _CAS_SUBDIR))


def _checkpoint_keep() -> int:
    try:
        return int(os.environ.get(C.CHECKPOINT_KEEP_ENV,
                                  str(C.DEFAULT_CHECKPOINT_KEEP)))
    except ValueError:
        return C.DEFAULT_CHECKPOINT_KEEP


def _commit_async_default() -> bool:
    return os.environ.get(C.COMMIT_ASYNC_ENV, "1").lower() \
        not in ("0", "false", "off")


#: Live commit writers, so a same-process reader (tests; the in-process
#: elastic mode) can drain in-flight writes before walking the store.
_WRITERS: "weakref.WeakSet[_CommitWriter]" = weakref.WeakSet()


def _flush_writers_for(commit_dir: str,
                       timeout: Optional[float] = 60.0) -> None:
    for w in list(_WRITERS):
        if w.commit_dir == commit_dir:
            w.flush(timeout=timeout)


#: Post-commit hooks: ``fn(commit_dir, seq)`` called on the WRITER thread
#: after each manifest publish + retention sweep. The serving publisher
#: (serving/publisher.py) attaches its publish gate here so gate work
#: (manifest read-back, blob re-hash) runs off the step loop. Hook
#: exceptions are logged and swallowed — a broken hook must never kill
#: the commit writer.
_COMMIT_HOOKS: List[Callable[[str, int], None]] = []


def register_commit_hook(fn: Callable[[str, int], None]):
    """Register a post-commit hook; returns ``fn`` (decorator-friendly)."""
    _COMMIT_HOOKS.append(fn)
    return fn


def unregister_commit_hook(fn: Callable[[str, int], None]) -> bool:
    try:
        _COMMIT_HOOKS.remove(fn)
        return True
    except ValueError:
        return False


def _fire_commit_hooks(commit_dir: str, seq: int) -> None:
    for fn in list(_COMMIT_HOOKS):
        try:
            fn(commit_dir, seq)
        except Exception as err:    # noqa: BLE001 — must not kill the writer
            get_logger().error(
                "post-commit hook %r failed (seq=%s): %s", fn, seq, err)


class _CommitWriter:
    """Double-buffered background persister for one state object.

    ``submit()`` is the step-path half: consult the identity cache
    (an array leaf that is literally the SAME immutable ``jax.Array``
    object as last commit reuses its digest — zero transfer, zero
    serialization), take cheap on-device copies of changed array leaves
    and start their device→host DMA, then enqueue. The only blocking the
    step loop ever sees is back-pressure: the previous commit still in
    flight (depth-1 double buffer). The on-device copy — not the live
    array — is what the writer later reads, so donating the live buffer
    to the next jitted step cannot invalidate the snapshot.

    The writer half (a lazily-started daemon thread that exits when
    idle) finishes the host transfer, pickles each leaf, stores blobs by
    content address and publishes the manifest atomically LAST, then
    retention-sweeps (``HOROVOD_CHECKPOINT_KEEP``). A crash anywhere
    before the publish leaves the previous manifest as the restore point
    — never a torn one.
    """

    _IDLE_EXIT_S = 5.0

    def __init__(self, commit_dir: str, async_enabled: bool):
        self.commit_dir = commit_dir
        self.async_enabled = async_enabled
        self.store = _cas_store(commit_dir)
        self._cond = threading.Condition()
        self._job: Optional[Dict[str, Any]] = None
        self._thread: Optional[threading.Thread] = None
        self._last_error: Optional[BaseException] = None
        self._cache_treedef = None          # identity cache validity key
        self._cache: List[tuple] = []       # (leaf_ref|None, digest, nbytes)
        self._last_host_leaves: List[Any] = []
        _WRITERS.add(self)

    # -- step-path half ------------------------------------------------------

    @staticmethod
    def _device_copy(leaf):
        """Cheap asynchronous on-device copy with its host DMA started."""
        import jax.numpy as jnp
        try:
            snap = jnp.copy(leaf)
        except Exception:          # noqa: BLE001 — odd array types: live ref
            snap = leaf
        try:
            snap.copy_to_host_async()
        except Exception:          # noqa: BLE001 — optional fast path only
            pass
        return snap

    def submit(self, seq: int, payload: Dict[str, Any],
               on_snapshot: Optional[Callable[[Dict[str, Any]], None]] = None
               ) -> None:
        t0 = time.perf_counter()
        with self._cond:
            while self._job is not None:    # back-pressure: depth-1 buffer
                self._cond.wait()
        leaves, treedef = jax.tree_util.tree_flatten(payload)
        cache_ok = (self._cache_treedef is not None
                    and treedef == self._cache_treedef
                    and len(self._cache) == len(leaves)
                    and len(self._last_host_leaves) == len(leaves))
        plans = []
        for i, leaf in enumerate(leaves):
            if cache_ok and isinstance(leaf, jax.Array):
                prev_leaf, digest, nbytes = self._cache[i]
                if prev_leaf is leaf:
                    plans.append(("cached", digest, nbytes, leaf))
                    continue
            if isinstance(leaf, jax.Array):
                plans.append(("fetch", self._device_copy(leaf), leaf))
            else:
                plans.append(("host", copy.deepcopy(leaf), leaf))
        job = {"seq": int(seq), "treedef": treedef, "plans": plans,
               "on_snapshot": on_snapshot}
        if not self.async_enabled:
            try:
                self._run_job(job)
            finally:
                _telemetry.observe("hvd_commit_stall_seconds",
                                   time.perf_counter() - t0)
            return
        with self._cond:
            self._job = job
            _telemetry.set_gauge("hvd_commit_inflight", 1.0)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._writer_loop, name="hvd-commit-writer",
                    daemon=True)
                self._thread.start()
            self._cond.notify_all()
        _telemetry.observe("hvd_commit_stall_seconds",
                           time.perf_counter() - t0)

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait until no commit is in flight; False on timeout or when
        the last background write failed (already logged)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._job is not None:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                self._cond.wait(timeout=remaining)
            ok = self._last_error is None
            self._last_error = None
            return ok

    # -- writer half ---------------------------------------------------------

    def _writer_loop(self) -> None:
        while True:
            with self._cond:
                deadline = time.monotonic() + self._IDLE_EXIT_S
                while self._job is None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return      # idle: exit; the next submit restarts us
                    self._cond.wait(timeout=remaining)
                job = self._job
            try:
                self._run_job(job)
            except BaseException as err:    # noqa: BLE001 — must not die
                self._last_error = err
                _telemetry.inc("hvd_commit_write_failures_total")
                get_logger().error(
                    "async commit write failed (seq=%s): %s — the previous "
                    "manifest remains the restore point",
                    job.get("seq"), err)
            finally:
                with self._cond:
                    self._job = None
                    _telemetry.set_gauge("hvd_commit_inflight", 0.0)
                    self._cond.notify_all()

    def _run_job(self, job: Dict[str, Any]) -> None:
        import numpy as np
        t0 = time.perf_counter()
        host_leaves: List[Any] = []
        entries: List[list] = []
        new_cache: List[tuple] = []
        bytes_written = bytes_deduped = 0
        for i, plan in enumerate(job["plans"]):
            kind = plan[0]
            if kind == "cached":
                _, digest, nbytes, orig = plan
                host_leaves.append(self._last_host_leaves[i])
                entries.append([digest, nbytes])
                bytes_deduped += nbytes
                new_cache.append((orig, digest, nbytes))
                continue
            if kind == "fetch":
                _, dev, orig = plan
                val = np.asarray(jax.device_get(dev))
            else:
                _, val, orig = plan
            blob = pickle.dumps(val, protocol=4)
            digest, wrote = self.store.put_blob(blob)
            if wrote:
                bytes_written += len(blob)
            else:
                bytes_deduped += len(blob)
            host_leaves.append(val)
            entries.append([digest, len(blob)])
            # Only IMMUTABLE leaves join the identity cache — a mutated
            # numpy buffer keeps its object id and must re-hash.
            new_cache.append((orig if isinstance(orig, jax.Array) else None,
                              digest, len(blob)))
        skeleton = jax.tree_util.tree_unflatten(
            job["treedef"], [_LeafRef(i) for i in range(len(entries))])
        skel_blob = pickle.dumps(skeleton, protocol=4)
        skel_digest, wrote = self.store.put_blob(skel_blob)
        if wrote:
            bytes_written += len(skel_blob)
        else:
            bytes_deduped += len(skel_blob)
        try:
            topo = {"process_index": jax.process_index(),
                    "process_count": jax.process_count()}
        except Exception:           # noqa: BLE001 — metadata only
            topo = {}
        # Chaos seam (testing/faults.py `torn` kind): die HERE — blobs
        # durable, manifest not yet published — to prove restores land on
        # the previous complete manifest, never a mixed one.
        if os.environ.get("HOROVOD_FAULT_SPEC"):
            from ..testing import faults as _faults
            _faults.maybe_torn_commit()
        self.store.publish_manifest({
            "seq": job["seq"], "skeleton": skel_digest, "leaves": entries,
            "topology": topo,
        })
        self.store.gc(_checkpoint_keep())
        self._cache_treedef = job["treedef"]
        self._cache = new_cache
        self._last_host_leaves = host_leaves
        _telemetry.inc("hvd_checkpoint_bytes_written_total", bytes_written)
        _telemetry.inc("hvd_checkpoint_bytes_deduped_total", bytes_deduped)
        _telemetry.set_gauge("hvd_last_manifest_seq", float(job["seq"]))
        _telemetry.observe("hvd_commit_write_seconds",
                           time.perf_counter() - t0)
        _telemetry.record_event("manifest_publish", seq=job["seq"],
                                bytes_written=bytes_written,
                                bytes_deduped=bytes_deduped)
        if job["on_snapshot"] is not None:
            job["on_snapshot"](jax.tree_util.tree_unflatten(
                job["treedef"], host_leaves))
        _fire_commit_hooks(self.commit_dir, int(job["seq"]))


def _path_name(entry) -> str:
    """One jax tree-path entry as a plain name (DictKey.key /
    GetAttrKey.name / SequenceKey.idx) — the shared leaf-keying scheme of
    the per-shard CAS layer (serving/publisher.py writes ``shards`` with
    it; the registry and the resume path select with it)."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _select_parts(manifest: Dict[str, Any], digest: str, names: tuple,
                  shard_selector) -> Optional[List[int]]:
    """Part indices of leaf ``digest`` the target sharding wants, or None
    for the whole-leaf blob (no shards entry, no selector, or the
    selector declined — e.g. the manifest was sharded for a DIFFERENT
    topology and read-compatibility demands the whole-leaf fallback)."""
    if shard_selector is None:
        return None
    meta = (manifest.get("shards") or {}).get(digest)
    if meta is None:
        return None
    sel = shard_selector(names, meta)
    if sel is None:
        return None
    return [int(i) for i in sel] or None


def _manifest_need(store, manifest: Dict[str, Any],
                   shard_selector=None) -> List[str]:
    """The digests THIS rank must hold to materialize the manifest under
    ``shard_selector`` — whole-leaf blobs by default; for a shard-selected
    leaf only the selected PART blobs (the topology-change delta: a
    resharded target pulls its slices, never the whole tensor). Requires
    the skeleton blob to be local (fetch it first)."""
    skeleton = pickle.loads(store.get_blob(manifest["skeleton"]))
    flat, _ = jax.tree_util.tree_flatten_with_path(skeleton)
    entries = manifest["leaves"]
    need: List[str] = [manifest["skeleton"]]
    for path, ref in flat:
        if not isinstance(ref, _LeafRef):
            raise ValueError("manifest skeleton holds a non-ref leaf "
                             f"({type(ref).__name__})")
        digest = entries[ref.index][0]
        names = tuple(_path_name(p) for p in path)
        sel = _select_parts(manifest, digest, names, shard_selector)
        if sel is None:
            need.append(digest)
        else:
            meta = manifest["shards"][digest]
            need.extend(meta["parts"][i][0] for i in sel)
    return list(dict.fromkeys(need))


def _unpack_manifest(store, manifest: Dict[str, Any],
                     shard_selector=None) -> Dict[str, Any]:
    """Materialize a payload from a manifest. Every blob read re-hashes
    against its content address (verify-at-restore); a mismatch raises
    ``BlobIntegrityError`` upward and the caller walks to an older
    manifest. With ``shard_selector`` (topology-change restore), a leaf
    with a manifest ``shards`` entry the selector claims is assembled
    from its selected PART blobs (concatenated along the shard axis,
    mirroring serving/registry.py ``_materialize``) instead of the
    whole-leaf blob."""
    skeleton = pickle.loads(store.get_blob(manifest["skeleton"]))
    if shard_selector is None:
        refs, treedef = jax.tree_util.tree_flatten(skeleton)
        entries = manifest["leaves"]
        leaves = []
        for ref in refs:
            if not isinstance(ref, _LeafRef):
                raise ValueError("manifest skeleton holds a non-ref leaf "
                                 f"({type(ref).__name__})")
            leaves.append(pickle.loads(store.get_blob(entries[ref.index][0])))
        return jax.tree_util.tree_unflatten(treedef, leaves)
    import numpy as np
    flat, treedef = jax.tree_util.tree_flatten_with_path(skeleton)
    entries = manifest["leaves"]
    leaves = []
    for path, ref in flat:
        if not isinstance(ref, _LeafRef):
            raise ValueError("manifest skeleton holds a non-ref leaf "
                             f"({type(ref).__name__})")
        digest = entries[ref.index][0]
        names = tuple(_path_name(p) for p in path)
        sel = _select_parts(manifest, digest, names, shard_selector)
        if sel is None:
            leaves.append(pickle.loads(store.get_blob(digest)))
            continue
        meta = manifest["shards"][digest]
        parts = [np.asarray(pickle.loads(
            store.get_blob(meta["parts"][i][0]))) for i in sel]
        leaves.append(parts[0] if len(parts) == 1 else np.concatenate(
            parts, axis=int(meta.get("axis", 0))))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _load_cas(commit_dir: str):
    """Newest readable content-addressed commit: ``(payload, manifest)``
    or ``(None, None)``. Digest mismatches and torn manifests are LOUD
    (error log) and fall back to the previous complete manifest."""
    from ..checkpoint.store import BlobIntegrityError
    store = _cas_store(commit_dir)
    for seq in reversed(store.manifest_seqs()):
        manifest = store.read_manifest(seq)
        if manifest is None:
            get_logger().error(
                "commit manifest %d in %s is torn/unreadable — falling "
                "back to an older manifest", seq, commit_dir)
            continue
        try:
            return _unpack_manifest(store, manifest), manifest
        except BlobIntegrityError as err:
            get_logger().error(
                "commit manifest %d failed content-address verification "
                "(%s) — falling back to an older manifest", seq, err)
        except Exception as err:    # noqa: BLE001 — missing blob, bad pickle
            get_logger().error(
                "commit manifest %d unreadable (%s) — falling back to an "
                "older manifest", seq, err)
    return None, None


#: commit dirs whose legacy-frame restore already logged the one-time
#: migration note.
_MIGRATION_NOTED: set = set()


def _load_local_commit(commit_dir: str) -> Optional[Dict[str, Any]]:
    """Newest verified LOCAL commit with its provenance:
    ``{"payload", "seq", "manifest"}`` (``manifest`` None for legacy
    single-frame commits), or None."""
    _flush_writers_for(commit_dir)
    cas_payload, manifest = _load_cas(commit_dir)
    legacy = _load_verified(_commit_path(commit_dir))
    if legacy is None:
        legacy = _load_verified(_prev_commit_path(commit_dir))
        if legacy is not None and cas_payload is None:
            get_logger().warning(
                "newest commit in %s unreadable — falling back to the "
                "previous committed generation (seq=%s)", commit_dir,
                legacy.get("seq"))
    if cas_payload is None and legacy is None:
        return None
    use_legacy = cas_payload is None or (
        legacy is not None
        and int(legacy.get("seq", 0)) > int(cas_payload.get("seq", 0)))
    if use_legacy:
        if commit_dir not in _MIGRATION_NOTED:
            _MIGRATION_NOTED.add(commit_dir)
            get_logger().info(
                "restored a legacy single-frame commit from %s (seq=%s); "
                "future commits write the content-addressed store under "
                "%s/%s — the frames stay readable but are ignored once a "
                "newer manifest exists", commit_dir, legacy.get("seq"),
                commit_dir, _CAS_SUBDIR)
        return {"payload": legacy, "seq": int(legacy.get("seq", 0)),
                "manifest": None}
    return {"payload": cas_payload, "seq": int(cas_payload.get("seq", 0)),
            "manifest": manifest}


def load_persisted(commit_dir: str) -> Optional[Dict[str, Any]]:
    """The newest VERIFIED local commit: content-addressed manifests
    preferred, legacy single-frame commits (``state.latest.pkl`` /
    ``state.prev.pkl``) still restored via the same newest→oldest walk."""
    local = _load_local_commit(commit_dir)
    return None if local is None else local["payload"]


#: Per-rank accounting of the last peer-sourced resume (bytes fetched,
#: retries, per-source blob counts, topology delta) — chaos workers and
#: the byte-accounting tests read it after ``load_latest``.
_LAST_RESUME_STATS: Dict[str, Any] = {}


def last_resume_stats() -> Dict[str, Any]:
    """Accounting of this process's most recent ``load_persisted_world``
    peer fetch (empty before the first resume)."""
    return dict(_LAST_RESUME_STATS)


def load_persisted_world(commit_dir: str,
                         shard_selector=None) -> Optional[Dict[str, Any]]:
    """The newest persisted commit across ALL processes of the (re)launched
    world. A relaunched generation may have a different process 0 whose
    disk never saw a commit (lost-host recovery); every process reports its
    local commit sequence number and the highest one wins.

    Fault-tolerant peer-sourced resume (elastic/blobmesh.py): the winning
    rank ships only its small MANIFEST; every rank then materializes
    leaves from its LOCAL blob store (shared disks and peer-identical
    content make most blobs local hits) and fetches ONLY ITS OWN missing
    digests point-to-point from digest-elected peers — sources spread
    across every rank that possesses a blob (the former single owner is
    just a tie-break), with retry/backoff, re-election away from dead or
    corrupt sources, and the whole resume bounded by
    ``HOROVOD_RESUME_TIMEOUT_SECONDS``. With ``shard_selector``
    (topology-change restore — regrown process count, reshaped tp), a
    leaf carried in the manifest ``shards`` map moves as the selected
    PART blobs only; mismatched plans fall back to the whole-leaf blob.
    Legacy single-frame owners fall back to the upstream-style
    whole-payload broadcast-on-reset."""
    local = _load_local_commit(commit_dir) if commit_dir else None
    if jax.process_count() == 1:
        if local is None:
            return None
        if shard_selector is not None and local["manifest"] is not None:
            return _unpack_manifest(_cas_store(commit_dir),
                                    local["manifest"], shard_selector)
        return local["payload"]
    import numpy as np
    from jax.experimental import multihost_utils
    from ..optimizer.functions import allgather_object, broadcast_object
    from . import blobmesh as _mesh
    t_start = time.monotonic()
    deadline_s = _mesh.resume_deadline_s()
    deadline = None if deadline_s <= 0 else t_start + deadline_s
    seq = -1 if local is None else int(local["seq"])
    seqs = multihost_utils.process_allgather(np.asarray([seq], np.int64))
    seqs = np.asarray(seqs).reshape(-1)
    owner = int(np.argmax(seqs))
    if seqs[owner] < 0:
        return None
    me = jax.process_index()
    head = broadcast_object(
        None if local is None else {"seq": local["seq"],
                                    "manifest": local["manifest"]},
        root_rank=owner)
    if head is None:
        return None
    manifest = head.get("manifest")
    if manifest is None:
        # Legacy single-frame owner: whole-payload broadcast (upstream's
        # elastic broadcast-on-reset, PARITY.md).
        return broadcast_object(
            None if local is None else local["payload"], root_rank=owner)
    store = _cas_store(commit_dir)
    topo = manifest.get("topology") or {}
    topo_np = int(topo.get("process_count", 0) or 0)
    if topo_np and topo_np != jax.process_count():
        get_logger().info(
            "topology-change restore: manifest seq=%s committed by a "
            "%d-process world, restoring into %d processes",
            manifest.get("seq"), topo_np, jax.process_count())
    # Any digest the manifest can reference, selector-independent — the
    # possession exchange covers the superset so election never needs a
    # second collective round once the skeleton lands.
    all_digests = [manifest["skeleton"]] + [e[0] for e in manifest["leaves"]]
    for meta in (manifest.get("shards") or {}).values():
        all_digests.extend(p[0] for p in meta["parts"])
    all_digests = list(dict.fromkeys(all_digests))
    possessed = [d for d in all_digests if store.has_blob(d)]
    key = _mesh.mesh_key(commit_dir)
    service = _mesh.BlobPeerService(store, key, rank=me)
    stats: Dict[str, Any] = {"blobs_fetched": 0, "bytes_fetched": 0,
                             "retries": 0, "sources": {},
                             "topology_from": topo_np or None,
                             "shard_selected": 0, "whole_leaf": 0}
    try:
        world = allgather_object({"rank": me, "addr": service.addr,
                                  "possess": possessed})
        possession = {int(w["rank"]): set(w["possess"]) for w in world}
        addrs = {int(w["rank"]): w["addr"] for w in world}
        # Pod-local preference: elect same-host possessors first (the
        # copy crosses loopback, not the fabric); host = the addr's host
        # part, mine taken from my own advertised serve addr.
        hosts = {r: a.rsplit(":", 1)[0] for r, a in addrs.items()}
        local_host = hosts.get(me)
        # The skeleton names the leaves; without it the selector cannot
        # run — fetch it first if missing (tiny blob, same failover).
        if not store.has_blob(manifest["skeleton"]):
            skel = [manifest["skeleton"]]
            s = _mesh.fetch_missing(
                store, skel,
                _mesh.assign_sources(skel, possession, owner,
                                     hosts=hosts, local_host=local_host),
                addrs, key, deadline=deadline)
            for k in ("blobs_fetched", "bytes_fetched", "retries"):
                stats[k] += s[k]
            for r, n in s["sources"].items():
                stats["sources"][r] = stats["sources"].get(r, 0) + n
        needed = _manifest_need(store, manifest, shard_selector)
        missing = [d for d in needed if not store.has_blob(d)]
        s = _mesh.fetch_missing(
            store, missing,
            _mesh.assign_sources(missing, possession, owner,
                                 hosts=hosts, local_host=local_host),
            addrs, key, deadline=deadline)
        for k in ("blobs_fetched", "bytes_fetched", "retries"):
            stats[k] += s[k]
        for r, n in s["sources"].items():
            stats["sources"][r] = stats["sources"].get(r, 0) + n
        # Completion barrier: keep every peer's service up until ALL
        # ranks finished fetching (a dead peer bounds out through the
        # engine's stall watchdog, not a hang).
        allgather_object({"rank": me, "done": True})
    finally:
        service.close()
    whole = set(e[0] for e in manifest["leaves"])
    stats["shard_selected"] = sum(1 for d in needed
                                  if d not in whole
                                  and d != manifest["skeleton"])
    stats["whole_leaf"] = sum(1 for d in needed if d in whole)
    stats["blobs_needed"] = len(needed)
    stats["blobs_missing"] = len(missing)
    _LAST_RESUME_STATS.clear()
    _LAST_RESUME_STATS.update(stats)
    _telemetry.record_event(
        "resume_fetch", manifest_seq=int(manifest["seq"]),
        blobs_total=len(needed), blobs_missing=len(missing),
        bytes_fetched=stats["bytes_fetched"], retries=stats["retries"],
        sources=len(stats["sources"]),
        topology_from=topo_np or jax.process_count(),
        topology_to=jax.process_count())
    return _unpack_manifest(store, manifest, shard_selector)


class _CommitterMixin:
    """Shared persistence plumbing for the concrete state classes:
    lazily-built :class:`_CommitWriter` + drain/telemetry helpers."""

    _commit_dir: Optional[str]
    _commit_async: bool

    def _committer(self) -> _CommitWriter:
        if self.__dict__.get("_writer") is None:
            self._writer = _CommitWriter(self._commit_dir,
                                         self._commit_async)
        return self._writer

    def flush_commits(self, timeout: Optional[float] = None) -> bool:
        """Drain the in-flight async commit (if any). run_fn calls this
        before a restart exit so the newest commit is durable for the
        relaunched generation."""
        w = self.__dict__.get("_writer")
        return True if w is None else w.flush(timeout=timeout)

    def _record_commit(self, seq: int) -> None:
        _telemetry.inc("hvd_commits_total")
        _telemetry.record_event("checkpoint_commit", seq=seq)

    def _record_restore(self, seq: int, t0: float) -> None:
        latency = time.perf_counter() - t0
        self._last_resume_latency_s = latency
        _telemetry.inc("hvd_restores_total")
        _telemetry.set_gauge("hvd_resume_latency_seconds", latency)
        _telemetry.record_event("checkpoint_restore", seq=seq,
                                latency_s=round(latency, 6))


class FrameworkState(_CommitterMixin, State):
    """Shared machinery for the framework-binding states (torch / tf):
    arbitrary scalar attributes, in-memory snapshots, disk-persisted
    commits (``HOROVOD_ELASTIC_COMMIT_DIR``) with ``load_latest`` for
    process-restart resume — so every framework state plugs into BOTH
    elastic modes (in-process reset and restart; elastic/run_fn.py).

    Subclasses own the framework half via three hooks:
    ``_framework_snapshot() -> picklable``, ``_framework_restore(snap)``,
    and ``_framework_broadcast()`` (make live state match rank 0).
    ``_GUARDED`` lists the subclass-owned attribute names exempt from the
    scalar-attr routing."""

    _GUARDED: tuple = ()

    def __init__(self, commit_dir: Optional[str] = None,
                 commit_async: Optional[bool] = None, **kwargs: Any):
        self._scalars: Dict[str, Any] = dict(kwargs)
        self._saved_scalars: Dict[str, Any] = dict(kwargs)
        self._commit_dir = commit_dir or os.environ.get(C.COMMIT_DIR_ENV)
        self._commit_async = (_commit_async_default() if commit_async is None
                              else bool(commit_async))
        self._writer: Optional[_CommitWriter] = None
        self._last_resume_latency_s: Optional[float] = None
        self._commit_seq = 0
        self._saved_fw: Any = None
        super().__init__()
        # In-memory snapshot only: persisting here would clobber a
        # previous generation's on-disk commit before load_latest().
        self._saved_fw = self._framework_snapshot()

    # -- scalar attribute routing (epoch=, batch=, ...) ----------------------

    def __getattr__(self, name):
        scalars = self.__dict__.get("_scalars", {})
        if name in scalars:
            return scalars[name]
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name.startswith("_") or name in type(self)._GUARDED:
            super().__setattr__(name, value)
        elif "_scalars" in self.__dict__ and name in self._scalars:
            self._scalars[name] = value
        else:
            super().__setattr__(name, value)

    # -- framework hooks -----------------------------------------------------

    def _framework_snapshot(self) -> Any:
        raise NotImplementedError

    def _framework_restore(self, snap: Any) -> None:
        raise NotImplementedError

    def _framework_broadcast(self) -> None:
        raise NotImplementedError

    def _broadcast_scalars(self, scalars: Dict[str, Any]) -> Dict[str, Any]:
        raise NotImplementedError

    # -- State contract ------------------------------------------------------

    def save(self) -> None:
        self._saved_fw = self._framework_snapshot()
        self._saved_scalars = dict(self._scalars)
        if self._commit_dir:
            self._commit_seq += 1
            # The snapshot is already host picklables; the writer hashes
            # and stores each leaf as a content-addressed blob off-thread
            # (unchanged leaves dedup by digest even without identity hits).
            self._committer().submit(
                self._commit_seq,
                {"seq": self._commit_seq, "fw": self._saved_fw,
                 "scalars": self._saved_scalars})
            self._record_commit(self._commit_seq)

    def restore(self) -> None:
        if self._saved_fw is not None:
            self._framework_restore(self._saved_fw)
        self._scalars = dict(self._saved_scalars)

    def load_latest(self, shard_selector=None) -> bool:
        """Adopt the newest persisted commit across the (re)launched
        world; returns True if one was found. ``shard_selector`` (see
        ``load_persisted_world``) enables topology-change restore via the
        manifest ``shards`` map."""
        if not self._commit_dir:
            return False
        t0 = time.perf_counter()
        payload = load_persisted_world(self._commit_dir,
                                       shard_selector=shard_selector)
        if payload is None:
            return False
        self._commit_seq = int(payload.get("seq", 0))
        self._saved_fw = payload.get("fw")
        self._saved_scalars = dict(payload.get("scalars", {}))
        self.restore()
        self._record_restore(self._commit_seq, t0)
        return True

    def sync(self) -> None:
        self._framework_broadcast()
        self._scalars = self._broadcast_scalars(self._scalars)
        self.save()


class ObjectState(_CommitterMixin, State):
    """State whose attrs are arbitrary picklable objects
    (reference: common/elastic.py ObjectState)."""

    #: attr names excluded from snapshots.
    _INTERNAL = ("_reset_callbacks", "_saved", "_commit_dir", "_commit_seq",
                 "_commit_async", "_writer", "_last_resume_latency_s")

    def __init__(self, commit_dir: Optional[str] = None,
                 commit_async: Optional[bool] = None, **kwargs):
        super().__init__()
        self._commit_dir = commit_dir or os.environ.get(C.COMMIT_DIR_ENV)
        self._commit_async = (_commit_async_default() if commit_async is None
                              else bool(commit_async))
        self._writer: Optional[_CommitWriter] = None
        self._last_resume_latency_s: Optional[float] = None
        self._commit_seq = 0
        self._saved: Dict[str, Any] = {}
        for k, v in kwargs.items():
            setattr(self, k, v)
        # In-memory snapshot only: persisting here would clobber a previous
        # generation's on-disk commit before load_latest() can adopt it.
        self._saved = self._snapshot()

    def _public_attrs(self) -> Dict[str, Any]:
        return {k: v for k, v in self.__dict__.items()
                if k not in self._INTERNAL}

    def _snapshot(self) -> Dict[str, Any]:
        return {k: self._host_copy(v) for k, v in self._public_attrs().items()}

    @staticmethod
    def _host_copy(v: Any) -> Any:
        """Device arrays → host numpy (survives mesh teardown); everything
        else deep-copied."""
        import numpy as np
        leaves, treedef = jax.tree_util.tree_flatten(v)
        out = []
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                out.append(np.asarray(jax.device_get(leaf)))
            else:
                out.append(copy.deepcopy(leaf))
        return jax.tree_util.tree_unflatten(treedef, out)

    def save(self) -> None:
        if not self._commit_dir:
            self._saved = self._snapshot()
            return
        self._commit_seq += 1

        def _adopt(host_payload: Dict[str, Any],
                   _self: "ObjectState" = self) -> None:
            _self._saved = host_payload["attrs"]

        # LIVE attr refs, not a host snapshot: the writer takes cheap
        # on-device copies of array leaves (identity-cache hits skip even
        # that) and finishes the host transfer + pickle off-thread; the
        # in-memory rollback snapshot (_saved) is adopted from the SAME
        # host leaves once written, so async == sync bit-for-bit.
        self._committer().submit(
            self._commit_seq,
            {"seq": self._commit_seq, "attrs": dict(self._public_attrs())},
            on_snapshot=_adopt)
        self._record_commit(self._commit_seq)

    def restore(self) -> None:
        # An in-flight async commit is adopting _saved from the writer
        # thread — drain it so we roll back to the NEWEST commit.
        self.flush_commits()
        for k, v in self._saved.items():
            setattr(self, k, copy.deepcopy(v) if not isinstance(v, jax.Array)
                    else v)

    def load_latest(self, shard_selector=None) -> bool:
        """Adopt the newest persisted commit across the world (process-
        restart resume; survives losing the former process 0's disk).
        Returns True if one was found. ``shard_selector`` (see
        ``load_persisted_world``) enables topology-change restore via the
        manifest ``shards`` map."""
        if not self._commit_dir:
            return False
        t0 = time.perf_counter()
        payload = load_persisted_world(self._commit_dir,
                                       shard_selector=shard_selector)
        if payload is None:
            return False
        self._commit_seq = int(payload.get("seq", 0))
        self._saved = payload.get("attrs", payload)
        self.restore()
        self._record_restore(self._commit_seq, t0)
        return True

    def sync(self) -> None:
        """Every process adopts process 0's attrs (reference: state.sync()
        broadcast from new rank 0). Broadcasts the HOST snapshot — live
        device buffers may be non-fully-addressable shards that cannot be
        pickled (and would be wrong to ship whole from one host anyway)."""
        from ..optimizer.functions import broadcast_object
        synced = broadcast_object(self._snapshot(), root_rank=0)
        for k, v in synced.items():
            setattr(self, k, v)
        self.save()


class JaxState(ObjectState):
    """``TorchState`` analog: model/optimizer pytrees + loop counters.

    Usage::

        state = JaxState(params=params, opt_state=opt_state,
                         epoch=0, batch=0)
        state.commit()                       # after each (few) step(s)
        params = state.params                # restored/synced on reset

    Arrays are snapshotted as host copies and restored as host numpy — the
    next jitted step re-places them onto the (possibly new) mesh, which is
    exactly what a post-reset recompile needs.
    """
