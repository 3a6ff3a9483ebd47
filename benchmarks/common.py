"""Shared machinery of the plane scripts in this directory (serving,
fleet, soak, checkpoint, collectives). None of them is a cell of the
benchmark: what decides a PR is ``python3 -m chipbench.run``
(docs/benchmarks.md). The scripts here:

- print one JSON line per metric: ``{"metric", "value", "unit",
  "vs_baseline"}``;
- time work by the SLOPE between a short and a long chain of calls, so
  constant host-dispatch latency cancels, with arms interleaved
  (:func:`slope_time_paired`).
"""

from __future__ import annotations

import json
import os
import sys
import time

# `python benchmarks/<x>.py` puts benchmarks/ (the script dir) on sys.path,
# not the repo root — add it so `import horovod_tpu` resolves in-repo.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

S_SHORT, S_LONG = 4, 16


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def sync(x) -> None:
    np.asarray(jax.tree_util.tree_leaves(x)[0]).ravel()[0]


def slope_time(run, s_short: int = S_SHORT, s_long: int = S_LONG,
               repeats: int = 5) -> float:
    """Seconds per unit from two chained-scan lengths (latency cancelled).

    ``run(k)`` must execute k units ending in a device->host sync.
    Host jitter is additive per measurement, so each absolute time is
    estimated as min-over-repeats before the slope is taken (a min of
    per-pair slopes would bias low — slope noise is two-sided).
    """
    return slope_time_paired({"_": run}, s_short, s_long,
                             rounds=repeats)["_"]


def slope_time_paired(runs: dict, s_short: int = S_SHORT,
                      s_long: int = S_LONG, rounds: int = 7,
                      return_rounds: bool = False):
    """``slope_time`` for several configs at once, interleaved.

    Measuring config A's repeats and then config B's lets slow drift in the
    device (other tenants, thermals) land entirely on one side and
    skew the A/B ratio. Here every round samples each (config, scan-length)
    once, in round-robin order, so drift is shared; the min over rounds per
    cell then cancels spike noise as in ``slope_time``. Returns
    ``{name: seconds-per-unit}``.

    ``return_rounds=True`` additionally returns the PER-ROUND slopes
    (``[{name: sec-per-unit}, ...]``): for A/B *ratios* take the median of
    per-round ratios — the min-over-rounds slopes may pair config A's
    quietest window with a different window of B's, skewing the ratio
    under bursty contention (measured: ratio read 0.88 in contended
    windows vs 1.00 quiet with min-pairing; round-local ratios stay ~1.0).
    """
    for fn in runs.values():  # warm all compiles before any timing
        fn(s_short)
        fn(s_long)
    best: dict = {(name, k): float("inf")
                  for name in runs for k in (s_short, s_long)}
    per_round = []
    for _ in range(rounds):
        times = {}
        for name, fn in runs.items():
            for k in (s_short, s_long):
                t0 = time.perf_counter()
                fn(k)
                dt = time.perf_counter() - t0
                times[(name, k)] = dt
                best[(name, k)] = min(best[(name, k)], dt)
        per_round.append(
            {name: max(times[(name, s_long)] - times[(name, s_short)], 1e-9)
             / (s_long - s_short) for name in runs})
    slopes = {name: max(best[(name, s_long)] - best[(name, s_short)], 1e-9)
              / (s_long - s_short) for name in runs}
    if return_rounds:
        return slopes, per_round
    return slopes


def median_ratio(rounds, num: str, den: str) -> float:
    """Median over rounds of ``slope[num]/slope[den]`` (statistics.median:
    averages the middle pair for even counts — a 2-round sample must not
    degenerate to max-pick). Rounds where either slope hit the 1e-9
    negative-clamp (timing jitter made long < short) are invalid — a
    clamped denominator would read as a ~1e9 ratio; falls back to the
    ratio of per-config MIN slopes when no round is clean.
    """
    import statistics
    valid = [r[num] / r[den] for r in rounds
             if r[num] > 2e-9 and r[den] > 2e-9]
    if valid:
        return float(statistics.median(valid))
    best_n = min(r[num] for r in rounds)
    best_d = min(r[den] for r in rounds)
    return best_n / best_d


def emit(metric: str, value: float, unit: str,
         vs_baseline: float | None = None, **extra) -> None:
    line = {"metric": metric, "value": round(float(value), 3), "unit": unit}
    if vs_baseline is not None:
        line["vs_baseline"] = round(float(vs_baseline), 4)
    line.update({k: v for k, v in extra.items() if v is not None})
    print(json.dumps(line), flush=True)
