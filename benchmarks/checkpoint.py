"""Checkpoint commit-stall A/B: async pipelined commits vs synchronous.

Reference analog: the reference measures elastic commit overhead as the
in-loop ``state.commit()`` wall time (horovod/common/elastic docs,
SURVEY.md §3.4); here the commit path is pipelined
(``elastic/state.py::_CommitWriter``) and this script proves the pipeline
actually removes the stall instead of hiding it.

Three arms over the same jitted train step (params sharded over every
local device — the 8-virtual-CPU mesh under the tier-1 env, real chips
on TPU), interleaved by ``common.slope_time_paired`` so tenant
drift lands on every arm equally. One UNIT = a cadence block of
``COMMIT_EVERY`` steps + one ``commit()`` (windows therefore can't
cherry-pick the commit-free phase — the slope-cadence trap by
construction):

- ``base``  — steps only (the device floor both commit arms share);
- ``sync``  — ``commit()`` inline: ``device_get`` DRAINS the dispatch
  pipeline, then pickle + blake2b + blob write, all on the step loop;
- ``async`` — ``commit()`` submits an on-device copy and returns; the
  background writer fetches/serializes off-loop, and the loop blocks
  only on back-pressure (previous commit still in flight).

The PRIMARY metric is the commit STALL — wall time the step loop spends
blocked inside ``commit()``, sampled per commit inside the interleaved
arms — because that is the cost the async writer exists to remove. Each
unit drains the dispatch queue before its commit: on a device-bound loop
any per-commit blocking point otherwise aliases to the device cadence
(sync's ``device_get`` and async's depth-1 back-pressure both read ~one
block of compute), so the sample must start from a quiesced device to
expose the commit path itself; the drain sits inside the timed wall of
every arm, so the slopes stay comparable.
End-to-end wall slopes are reported alongside: on a single-core host
(this CI box: 8 virtual devices on 1 core) the writer's CPU work is
conserved no matter which thread runs it, so the wall ratio reads ~1.0
by physics; on real TPU the step compute is on-chip and the freed stall
is the wall saving.

Dedup: the state carries a FROZEN leaf (~8x the trained leaf). The
content-addressed store writes it once; every later commit re-manifests
its digest via the writer's identity cache. ``dedup_bytes_ratio`` =
total bytes actually written / (commits x first-commit bytes) — a
frame-per-commit checkpointer scores 1.0, the CAS must score well under.

Prints one JSON line: ``checkpoint_commit_stall``
ratio (async/sync, median of interleaved samples) with the wall slopes,
dedup ratio and cold ``load_latest`` resume latency as extra fields.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python benchmarks/checkpoint.py
"""

from __future__ import annotations

import statistics
import tempfile
import time

from common import emit, median_ratio, slope_time_paired, sync

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.elastic.state import ObjectState

TRAINED_DIM = 512          # 512x512 f32 = 1 MiB trained leaf
FROZEN_MB = 8              # frozen leaf ~8x the trained one
COMMIT_EVERY = 4           # steps per commit (one cadence block = 1 unit)
ROUNDS = 7
DEDUP_COMMITS = 6


def _mesh() -> Mesh:
    return Mesh(np.array(jax.devices()), ("d",))


def _make_step(mesh: Mesh):
    shard = NamedSharding(mesh, P("d"))

    @jax.jit
    def step(w):
        # shard-local elementwise chain: enough device work per cadence
        # block that the inline checkpoint write is a visible fraction of
        # it, but NO cross-device collectives — XLA CPU's 8-thread
        # rendezvous starves when the background writer's fetches compete
        # for the single-core executor pool (collective modules deadlock)
        for _ in range(24):
            w = w - 1e-4 * jnp.tanh(w) * w
        return w

    w0 = jax.device_put(
        np.random.RandomState(0).randn(TRAINED_DIM, TRAINED_DIM)
        .astype(np.float32), shard)
    frozen = jax.device_put(
        np.random.RandomState(1).randn(FROZEN_MB * TRAINED_DIM // 4,
                                       TRAINED_DIM)
        .astype(np.float32), shard)
    return step, w0, frozen


def _commit_arm(step, w0, frozen, commit_async: bool, stalls: list):
    d = tempfile.mkdtemp(prefix="hvd_ckpt_bench_")
    state = ObjectState(commit_dir=d, commit_async=commit_async,
                        step=0, w=w0, frozen=frozen)

    def run(k: int) -> None:
        w = state.w
        for _ in range(k):
            for _ in range(COMMIT_EVERY):
                w = step(w)
            # drain the dispatch queue BEFORE sampling the stall: when the
            # loop is device-throughput-bound, ANY per-commit blocking
            # point aliases to the device cadence (sync's device_get and
            # async's depth-1 back-pressure both read ~one block), so the
            # stall sample must start from a quiesced device to measure
            # the commit path itself — the drain is inside the timed wall
            # of BOTH commit arms and the base arm, so slopes stay fair
            sync(w)
            state.w = w          # live handoff: the writer fetches off-loop
            state.step += COMMIT_EVERY
            t0 = time.perf_counter()
            state.commit()
            stalls.append(time.perf_counter() - t0)
        # drain before the NEXT interleaved cell so a leftover background
        # write can't bleed into another arm's window (counted here: the
        # at-most-one in-flight job is this arm's own work)
        state.flush_commits(timeout=60)

    return state, run


def _base_arm(step, w0):
    holder = {"w": w0}

    def run(k: int) -> None:
        w = holder["w"]
        for _ in range(k):
            for _ in range(COMMIT_EVERY):
                w = step(w)
            sync(w)          # same per-unit drain as the commit arms
        holder["w"] = w

    return run


def _dedup_and_resume() -> tuple:
    """(bytes-written ratio vs frame-per-commit, cold resume seconds)."""
    d = tempfile.mkdtemp(prefix="hvd_ckpt_dedup_")
    mesh = _mesh()
    step, w0, frozen = _make_step(mesh)
    state = ObjectState(commit_dir=d, commit_async=True,
                        step=0, w=w0, frozen=frozen)
    w = w0
    first = None
    for _ in range(DEDUP_COMMITS):
        w = step(w)
        state.w = w
        state.step += 1
        state.commit()
        if first is None:
            assert state.flush_commits(timeout=60)
            # stats live on the WRITER's store; reader instances start at 0
            first = state._writer.store.stats["bytes_written"]
    assert state.flush_commits(timeout=60)
    total = state._writer.store.stats["bytes_written"]
    ratio = total / float(DEDUP_COMMITS * first)

    cold = ObjectState(commit_dir=d, step=0, w=None, frozen=None)
    assert cold.load_latest()
    assert int(cold.step) == DEDUP_COMMITS
    np.testing.assert_array_equal(np.asarray(cold.w),
                                  np.asarray(jax.device_get(w)))
    return ratio, float(cold._last_resume_latency_s)


def main() -> None:
    mesh = _mesh()
    step, w0, frozen = _make_step(mesh)
    sync_stalls: list = []
    async_stalls: list = []
    _, run_sync = _commit_arm(step, w0, frozen, False, sync_stalls)
    _, run_async = _commit_arm(step, w0, frozen, True, async_stalls)
    run_base = _base_arm(step, w0)

    slopes, rounds = slope_time_paired(
        {"base": run_base, "sync": run_sync, "async": run_async},
        rounds=ROUNDS, return_rounds=True)

    # the warmup pass compiles AND populates the writer identity cache /
    # first frozen-leaf blob; drop its stall samples (first-commit cost is
    # the dedup phase's business, not the steady-state stall's)
    warm = 4 + 16                # one warm call per window = 20 commits
    sync_stall = statistics.median(sync_stalls[warm:] or sync_stalls)
    async_stall = statistics.median(async_stalls[warm:] or async_stalls)
    stall_ratio = async_stall / max(sync_stall, 1e-9)
    dedup_ratio, resume_s = _dedup_and_resume()

    emit("checkpoint_commit_stall", stall_ratio, "x_vs_sync",
         sync_stall_ms=round(sync_stall * 1e3, 3),
         async_stall_ms=round(async_stall * 1e3, 3),
         base_ms=round(slopes["base"] * 1e3, 3),
         sync_ms=round(slopes["sync"] * 1e3, 3),
         async_ms=round(slopes["async"] * 1e3, 3),
         wall_async_vs_sync=round(median_ratio(rounds, "async", "sync"), 4),
         dedup_bytes_ratio=round(dedup_ratio, 4),
         resume_latency_s=round(resume_s, 6))


if __name__ == "__main__":
    main()
