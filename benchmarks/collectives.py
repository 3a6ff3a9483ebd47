"""Collective bus-bandwidth microbenchmark.

BASELINE north star: ≥90% ICI bus-bandwidth utilization. Sweeps message
sizes through in-graph allreduce / allgather / alltoall / reducescatter
over the mesh rank axis and reports **bus bandwidth** with the standard
ring-algorithm formulas (NCCL-tests convention, so numbers compare
directly to the reference's GPU reports):

    allreduce:      busBW = 2(n-1)/n · bytes / t
    allgather:      busBW = (n-1)/n · total_bytes / t
    reducescatter:  busBW = (n-1)/n · in_bytes / t
    alltoall:       busBW = (n-1)/n · bytes / t

Each op is timed as a DEPENDENT chain inside ``lax.scan`` (output feeds the
next input) so XLA cannot hoist or overlap away the transfers; wall time
comes from the slope between two chain lengths (common.py).

Set ``HOROVOD_BENCH_ICI_PEAK_GBPS`` (per-chip bidirectional ICI, GB/s) to
also report utilization as ``vs_baseline``; hardware peaks differ per TPU
generation, so none is assumed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from common import (emit, median_ratio, on_tpu, slope_time,
                    slope_time_paired, sync)


def sweep_fusion():
    """``--sweep-fusion``: interleaved HOROVOD_FUSION_THRESHOLD sweep.

    Times a grouped (fused) gradient-shaped allreduce — a pytree of mixed
    leaf sizes mimicking a model's grads — under 2–3 bucket sizes applied
    via ``fusion_threshold_override`` at trace time, INTERLEAVED through
    ``slope_time_paired`` (never time arms in separate blocks). Prints a per-size ratio table against the uncapped
    single-buffer arm so bucket tuning is a reproducible artifact instead
    of folklore. In this chained microbench the collectives have no
    backward compute to hide behind — the table isolates the pure
    bucketing overhead (launch/rendezvous per bucket); whether a bucket's
    reduce hides under backward shows only in a training cell's trace
    (``collective_exposed_ms`` of ``bert-large-s512-dp4``).
    """
    import horovod_tpu as hvd
    from horovod_tpu.collectives import ops
    from horovod_tpu.collectives.ops import fusion_threshold_override

    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()
    axis = hvd.RANK_AXIS
    if n == 1:
        emit("fusion_sweep", 0.0, "skipped (1 rank)")
        return
    # Gradient-shaped tree: a few big leaves + a tail of small ones
    # (the realistic shape: conv/matmul kernels + biases/norm scales).
    if on_tpu():
        big, small, n_small = 4 << 20, 16 << 10, 24   # ~17 MB/device
        thresholds = [("uncapped", 1 << 62), ("4mb", 4 << 20),
                      ("256kb", 256 << 10)]
    else:
        big, small, n_small = 256 << 10, 4 << 10, 12  # CPU mesh: ~1.1 MB
        thresholds = [("uncapped", 1 << 62), ("64kb", 64 << 10),
                      ("8kb", 8 << 10)]
    leaves = [jnp.ones((big // 4,), jnp.float32) for _ in range(4)] + \
             [jnp.ones((small // 4,), jnp.float32) for _ in range(n_small)]
    total_mb = sum(l.size * 4 for l in leaves) / (1 << 20)

    def make_run(thr):
        def chained(k):
            def fn(tree):
                def one(c, _):
                    return ops.grouped_allreduce(c, ops.Sum), ()
                c, _ = lax.scan(one, tree, None, length=k)
                return c
            # Leaves replicated (P() prefix-broadcasts over the tree):
            # grads are replicated per-device in the DP step too.
            return jax.jit(shard_map(
                fn, mesh=mesh, in_specs=P(), out_specs=P(),
                check_vma=False))
        with fusion_threshold_override(thr):
            fns = {k: chained(k) for k in (2, 8)}
            for f in fns.values():
                sync(f(leaves))  # compile under the override

        def run(k):
            sync(fns[k](leaves))
        return run

    runs = {name: make_run(thr) for name, thr in thresholds}
    times, rounds = slope_time_paired(runs, s_short=2, s_long=8,
                                      return_rounds=True)
    print(f"\nfusion sweep: {len(leaves)} leaves, {total_mb:.1f} MB/device, "
          f"{n} ranks (ratio >1 = faster than uncapped)")
    print(f"{'threshold':<10} {'ms/allreduce':>14} {'ratio_vs_uncapped':>19}")
    for name, _ in thresholds:
        ratio = median_ratio(rounds, "uncapped", name)
        print(f"{name:<10} {times[name]*1e3:>14.3f} {ratio:>19.3f}")
        emit(f"fusion_sweep_{name}", times[name] * 1e3, "ms/op",
             ratio if name != "uncapped" else None)


def main():
    import horovod_tpu as hvd
    from horovod_tpu.collectives import ops

    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()
    axis = hvd.RANK_AXIS
    peak = float(os.environ.get("HOROVOD_BENCH_ICI_PEAK_GBPS", "0")) or None
    if n == 1:
        # Bus-bandwidth formulas are 0 at n=1; nothing rides the wire.
        emit("collectives_busbw", 0.0,
             "GB/s (1 rank — run on a multi-chip mesh)")
        return

    sizes_mb = [1, 8, 64] if on_tpu() else [1]

    def time_chain(body, shard_elems, k_short=2, k_long=8):
        """Seconds per op for body: (shard,) -> (shard,) chained k times."""
        x = jnp.ones((n * shard_elems,), jnp.float32)

        def make(k):
            def chained(v):
                def one(c, _):
                    return body(c), ()
                c, _ = lax.scan(one, v, None, length=k)
                return c
            return jax.jit(shard_map(chained, mesh=mesh, in_specs=P(axis),
                                     out_specs=P(axis), check_vma=False))

        fns = {k: make(k) for k in (k_short, k_long)}

        def run(k):
            sync(fns[k](x))
        return slope_time(run, k_short, k_long)

    for mb in sizes_mb:
        elems = mb * (1 << 20) // 4          # per-shard payload elements
        bytes_ = elems * 4

        # allreduce: (elems,) -> (elems,), dependent by construction.
        t = time_chain(lambda v: ops.allreduce(v, ops.Sum), elems)
        bw = 2 * (n - 1) / n * bytes_ / t / 1e9
        emit(f"allreduce_busbw_{mb}mb", bw, f"GB/s ({n} ranks)",
             None if peak is None else bw / peak)

        # allgather: gather to (n*elems,), keep own chunk -> (elems,).
        def ag_body(v):
            g = ops.allgather(v)
            i = lax.axis_index(axis)
            return lax.dynamic_slice(g, (i * v.shape[0],), (v.shape[0],))
        t = time_chain(ag_body, elems)
        bw = (n - 1) / n * bytes_ * n / t / 1e9
        emit(f"allgather_busbw_{mb}mb", bw, f"GB/s ({n} ranks)",
             None if peak is None else bw / peak)

        # alltoall: (elems,) -> (elems,) when elems % n == 0.
        a2a_elems = (elems // n) * n
        t = time_chain(lambda v: ops.alltoall(v), a2a_elems)
        bw = (n - 1) / n * a2a_elems * 4 / t / 1e9
        emit(f"alltoall_busbw_{mb}mb", bw, f"GB/s ({n} ranks)",
             None if peak is None else bw / peak)

        # reducescatter: (elems,) -> (elems/n,), tiled back up to keep the
        # chain shape-stable (adds one cheap HBM pass vs the transfer).
        def rs_body(v):
            r = ops.reducescatter(v, ops.Sum)
            return jnp.tile(r, n)[:v.shape[0]]
        t = time_chain(rs_body, a2a_elems)
        bw = (n - 1) / n * a2a_elems * 4 / t / 1e9
        emit(f"reducescatter_busbw_{mb}mb", bw, f"GB/s ({n} ranks)",
             None if peak is None else bw / peak)


if __name__ == "__main__":
    if "--sweep-fusion" in sys.argv:
        sweep_fusion()
    else:
        main()
