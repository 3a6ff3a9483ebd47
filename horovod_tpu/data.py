"""Host-side input pipeline: shard batches over the mesh, prefetch to device.

Role: the reference leaves data loading to each framework (torch
``DataLoader`` + ``DistributedSampler``; its own ``ElasticSampler`` for
elastic runs — SURVEY.md §2.5). On TPU the input pipeline is a first-order
perf concern (HBM is fed over PCIe from the host): this module provides the
three host-side pieces a training loop needs, TPU-shaped:

- :func:`shard_batch` — host-local numpy → a global ``jax.Array`` laid out
  batch-over-rank-axis on the mesh (one process per host contributes its
  local shard; single-process worlds take the in-process fast path).
- :class:`Prefetcher` — background-thread double buffering: the next
  batch's host→device transfer overlaps the current step's compute
  (the ``flax`` ``prefetch_to_device`` idiom, made mesh-aware).
- :class:`Dataset` — minimal array dataset: per-process sharding by
  ``cross_rank`` (the reference's ``DistributedSampler`` role), epoch
  shuffling, drop-last batching.
- :func:`sampler_batches` — the elastic glue: batches an
  :class:`~horovod_tpu.elastic.ElasticSampler`'s local shard and records
  progress, so commit/restore resumes mid-epoch after membership changes.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from .core import context_api as _ctx
from .core import telemetry as _telemetry


def shard_batch(batch: Any, mesh=None, axis: Optional[str] = None):
    """Per-process host batch (pytree of numpy arrays, leading dim = LOCAL
    batch) → global device array sharded over the mesh's rank axis.

    Multi-process: every process contributes its local shard
    (``multihost_utils.host_local_array_to_global_array``); the global
    leading dim is ``local_batch * process_count``. Single-process: one
    ``device_put`` with the sharded layout.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh if mesh is not None else _ctx.mesh()
    axis = axis or _ctx.context().axis_name
    spec = P(axis)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            batch, mesh, spec)
    sharding = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x), sharding), batch)


def _account_waits(waits: "collections.deque") -> None:
    """Record the hand-outs the consumer has timed and not yet accounted:
    the ``input.wait`` span, the data-wait gauge (the wait measured where it
    happens) and the ready share's two counters."""
    while True:
        try:
            seq, t0, t1, ready, thread = waits.popleft()
        except IndexError:  # empty, or the other drainer took the last
            return
        _telemetry.record_span("input.wait", seq, t0, t1, thread)
        _telemetry.set_gauge("hvd_step_data_wait_seconds", (t1 - t0) / 1e9)
        _telemetry.inc("hvd_input_batches_total")
        if ready:
            _telemetry.inc("hvd_input_ready_total")


def _prefetch_worker(it: Iterator, transfer: Callable, q: "queue.Queue",
                     stop: threading.Event, done: object,
                     waits: "collections.deque") -> None:
    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # The worker's busy time a batch is input.produce (the source: a
    # Dataset's gather) plus input.transfer (shard_batch: the host's share
    # of the copy, device_put returns before the DMA ends); both carry the
    # batch's ordinal, which input.wait carries when it is handed out. The
    # consumer only times its waits; this thread does their bookkeeping.
    try:
        seq = 0
        while True:
            _account_waits(waits)
            with _telemetry.span("input.produce", seq):
                batch = next(it, done)
            if batch is done:
                break
            _telemetry.inc("hvd_input_bytes_total", sum(
                getattr(x, "nbytes", 0)
                for x in jax.tree_util.tree_leaves(batch)))
            with _telemetry.span("input.transfer", seq):
                batch = transfer(batch)
            if stop.is_set() or not put(batch):
                return
            seq += 1
        put(done)
    except BaseException as e:  # re-raised on the consumer side
        put(e)


class Prefetcher:
    """Wrap a host-batch iterator; a worker thread runs ``transfer`` (by
    default :func:`shard_batch`) ``depth`` batches ahead so host→device
    copies overlap device compute.

    Iteration re-raises worker exceptions at the consumption point (a
    drained/failed Prefetcher then yields StopIteration, never hangs).
    The worker exits when the iterator ends, when ``close()`` is called,
    or when the Prefetcher is garbage-collected (its queue puts poll a
    stop flag, so an abandoned ``for``-loop cannot strand the thread
    holding device-sized batches). Usable as a context manager.
    """

    _DONE = object()

    def __init__(self, it: Iterable, depth: int = 2,
                 transfer: Optional[Callable] = None, mesh=None,
                 axis: Optional[str] = None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if transfer is None:
            def transfer(b):  # noqa: F811 — default is the mesh shard-put
                return shard_batch(b, mesh=mesh, axis=axis)
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._dead = False
        self._handed = 0  # ordinal of the next batch handed out
        # (seq, t0_ns, t1_ns, was ready, thread) of hand-outs not yet
        # accounted; the worker drains it, close() what is left
        self._waits: "collections.deque" = collections.deque()
        self._thread = threading.Thread(
            target=_prefetch_worker,
            args=(iter(it), transfer, self._q, self._stop, self._DONE,
                  self._waits),
            daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._dead:
            raise StopIteration
        timed = _telemetry.enabled()
        if timed:
            # Two clock reads and an append, then straight back to the
            # caller: this is the loop's thread, where Python run once a
            # step, cache-cold, costs ten times its timeit figure (the
            # span, gauge and counters here cost 0.05 ms a step on the
            # v5e's host, and 0.6 ms when they sat between get() and the
            # return; PERF.md section 6). The worker does the accounting.
            t0 = time.perf_counter_ns()
            with TraceAnnotation("hvd:input.wait"):
                try:
                    item, ready = self._q.get_nowait(), True
                except queue.Empty:
                    item, ready = self._q.get(), False
            t1 = time.perf_counter_ns()
        else:
            item = self._q.get()
        if item is self._DONE or isinstance(item, BaseException):
            self._dead = True  # next call: StopIteration, not a hang
            _account_waits(self._waits)  # the worker has gone
            if item is self._DONE:
                raise StopIteration
            raise item
        if timed:
            self._waits.append((self._handed, t0, t1, ready,
                                threading.get_ident()))
        self._handed += 1
        return item

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # abandoned mid-loop: don't strand the worker
        try:
            self.close()
        except Exception:
            pass

    def close(self) -> None:
        self._stop.set()
        self._dead = True

        def drain():
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass

        # Unblock a producer waiting on a full queue, then JOIN the worker
        # and drain again: the worker may complete a put (or an in-flight
        # transfer) concurrently with the first drain, and close() promises
        # no queued device-sized batch outlives it. The join timeout is
        # short: a worker blocked INSIDE the source iterator (slow next())
        # holds no queued buffer yet, and waiting longer would stall
        # __exit__/__del__ (GC) for a thread the stop flag will reap at its
        # next put anyway.
        drain()
        t = self._thread
        if t.is_alive() and t is not threading.current_thread():
            t.join(timeout=0.5)
        drain()
        _account_waits(self._waits)


class Dataset:
    """Array dataset with per-process sharding and epoch shuffling.

    ``arrays`` is a pytree of equal-leading-dim numpy arrays (e.g.
    ``(images, labels)``). Each PROCESS iterates its own contiguous slice
    of the shuffled global order (the reference ``DistributedSampler``
    contract: same seed ⇒ disjoint, exhaustive shards), yielding
    local batches of ``batch_size // process_count`` ready for
    :func:`shard_batch` / :class:`Prefetcher`.
    """

    def __init__(self, arrays: Any, batch_size: int, *, shuffle: bool = True,
                 seed: int = 0, drop_last: bool = True,
                 rank: Optional[int] = None,
                 num_replicas: Optional[int] = None):
        leaves = _leaves(arrays)
        if not leaves:
            raise ValueError("empty dataset pytree")
        self.n = leaves[0].shape[0]
        if any(l.shape[0] != self.n for l in leaves):
            raise ValueError("all leaves need the same leading dimension")
        self.arrays = arrays
        self.global_batch = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.rank = jax.process_index() if rank is None else rank
        self.num_replicas = (jax.process_count() if num_replicas is None
                             else num_replicas)
        if batch_size % self.num_replicas:
            raise ValueError(
                f"batch_size {batch_size} must divide over "
                f"{self.num_replicas} processes")
        self.local_batch = batch_size // self.num_replicas
        self.epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        steps = self.n // self.global_batch
        if not self.drop_last and self.n % self.global_batch:
            steps += 1
        return steps

    def __iter__(self):
        from . import native

        order = np.arange(self.n)
        if self.shuffle:
            np.random.RandomState(self.seed + self.epoch).shuffle(order)
        for s in range(len(self)):
            sel = order[s * self.global_batch:(s + 1) * self.global_batch]
            if len(sel) < self.global_batch:
                # drop_last=False tail: pad to the FULL global batch by
                # wrapping from the front of the epoch order (the
                # DistributedSampler convention, taken one step further):
                # every process sees the same local size AND every step the
                # same shape, so a jitted train step never recompiles on
                # the final batch.
                pad = self.global_batch - len(sel)
                sel = np.concatenate([sel, np.resize(order, pad)])
            per = len(sel) // self.num_replicas
            mine = sel[self.rank * per:(self.rank + 1) * per]
            # Native threaded gather (GIL-free memcpy; ~9x numpy fancy
            # indexing on image-sized batches) — numpy fallback inside.
            yield _map_leaves(lambda a: native.parallel_gather(a, mine),
                              self.arrays)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _map_leaves(fn, tree):
    return jax.tree_util.tree_map(lambda a: fn(np.asarray(a)), tree)


def sampler_batches(sampler, arrays: Any, local_batch: int, *,
                    drop_last: bool = True):
    """Iterate an :class:`~horovod_tpu.elastic.ElasticSampler`'s LOCAL
    shard as host batches — the elastic-training input glue: the sampler
    owns ordering (commit/restore survives membership changes), this
    yields ``local_batch``-sized pytree slices via the native gather.

    Progress recording is the TRAINING LOOP's job, after the step that
    actually consumed the batch (the reference contract:
    ``sampler.record_batch(step, batch_size)`` then ``state.commit()``).
    Recording here at production time would mark batches sitting in a
    :class:`Prefetcher` queue as processed — a commit then persists
    untrained examples as done, and an elastic restore silently skips
    them.

    Compose::

        for i, b in enumerate(Prefetcher(sampler_batches(s, (X, Y), 32))):
            state, loss = step(state, *b)
            s.record_batch(i, 32)
            st.commit()
    """
    from . import native

    idx = np.asarray(list(sampler), dtype=np.int64)
    steps = len(idx) // local_batch if drop_last \
        else -(-len(idx) // local_batch)
    for s in range(steps):
        sel = idx[s * local_batch:(s + 1) * local_batch]
        yield _map_leaves(lambda a: native.parallel_gather(a, sel), arrays)
