"""Torch multi-host engine payload-path microbench: device-backed XLA
reduction vs the pre-r2 gather-everything path (VERDICT r1 "what's weak" #2).

Run under a REAL multi-process launch:

    hvdrun -np 2 -H localhost:1,127.0.0.1:1 python benchmarks/torch_engine_bw.py

Rank 0 prints one JSON line per message size:
  {"metric": "torch_engine_allreduce", "size_mb": S,
   "device_ms": ..., "gather_ms": ..., "speedup": ...}

The device path runs ONE jitted XLA psum over the process mesh (ring wire
cost, on-device reduce); the gather path allgathers every rank's full
payload (size + padded-bytes rounds, N x wire bytes) and reduces in numpy.
The crossover to device-path wins moves down with process count and
payload size.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import torch  # noqa: F401  (torch API init expects it importable)

SIZES_MB = [0.25, 1, 4, 16]
REPEATS = 5


def time_op(fn) -> float:
    fn()  # warm (compile/cache)
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    import horovod_tpu as hvd
    from horovod_tpu import torch as thvd

    hvd.init()
    thvd.init()
    rt = thvd.mpi_ops._rt()
    eng = rt.engine
    if not hasattr(eng, "_gather_allreduce"):
        print(json.dumps({"error": "needs the multi-process JaxProcessEngine"
                          " (run under hvdrun -np 2)"}))
        return

    for i, mb in enumerate(SIZES_MB):
        n = int(mb * 1024 * 1024 / 4)
        arr = np.random.RandomState(i).randn(n).astype(np.float32)
        dev = time_op(lambda: eng.allreduce(f"bw.dev.{i}", arr, "sum"))
        gat = time_op(lambda: eng._gather_allreduce(f"bw.gat.{i}", arr,
                                                    "sum"))
        if thvd.rank() == 0:
            print(json.dumps({
                "metric": "torch_engine_allreduce", "size_mb": mb,
                "device_ms": round(dev * 1e3, 2),
                "gather_ms": round(gat * 1e3, 2),
                "speedup": round(gat / dev, 2),
            }), flush=True)

    # --- header-negotiation overhead A/B (VERDICT r2 weak #3) ---------------
    # Steady-state signature cache ON (one fixed 24-byte mini gather per op)
    # vs OFF (sizes gather + padded pickled-header gather per op), measured
    # on a SMALL payload so negotiation dominates. The cache is engine-state;
    # flushing _sig_seen and flipping _cache_capacity reproduces both
    # protocols in one process without relaunching.
    small = np.ones(64, dtype=np.float32)

    def run_cached():
        eng.allreduce("bw.hdr", small, "sum")

    saved_cap = eng._cache_capacity

    def run_uncached():
        eng._cache_capacity = 0
        try:
            eng.allreduce("bw.hdr.u", small, "sum")
        finally:
            eng._cache_capacity = saved_cap

    # NOTE: _cache_capacity must flip identically on every rank — both
    # closures run the same interleaved schedule on all ranks, so the
    # protocols stay in lockstep. Interleaved per-round pairs, median of
    # round-local ratios (the CLAUDE.md measurement rule: never two
    # separate timing blocks).
    run_cached()   # warm: populate the signature cache
    run_uncached()
    cached_ts, full_ts, ratios = [], [], []
    for _ in range(9):
        t0 = time.perf_counter()
        run_cached()
        t1 = time.perf_counter()
        run_uncached()
        t2 = time.perf_counter()
        cached_ts.append(t1 - t0)
        full_ts.append(t2 - t1)
        ratios.append((t2 - t1) / (t1 - t0))
    if thvd.rank() == 0:
        ratios.sort()
        cached_ts.sort()
        full_ts.sort()
        mid = len(ratios) // 2
        # All three fields are medians so the line is self-consistent
        # (speedup is the median of ROUND-LOCAL ratios, the contention-
        # proof statistic, so it may differ slightly from the quotient).
        print(json.dumps({
            "metric": "torch_engine_header_overhead",
            "cached_us": round(cached_ts[mid] * 1e6, 1),
            "full_round_us": round(full_ts[mid] * 1e6, 1),
            "speedup": round(ratios[mid], 2),
        }), flush=True)


if __name__ == "__main__":
    main()
