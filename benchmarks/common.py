"""Shared benchmark machinery.

Reference analog: the reference's ``horovod/benchmarks``-style scripts +
`docs/benchmarks.rst` methodology (SURVEY.md §6). All scripts here:

- print one JSON line per metric: ``{"metric", "value", "unit",
  "vs_baseline"}`` (the bench.py schema);
- time device work by the SLOPE between a short and a long ``lax.scan``
  (two chained-dispatch lengths), so constant host-dispatch latency
  cancels (ROADMAP S0 decides whether a local chip still needs this);
- auto-size DOWN on CPU meshes so the suite doubles as a shape/correctness
  check in CI (SURVEY.md §4 universal-fake-backend discipline).
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


def peak_flops(device=None) -> float:
    """Per-chip bf16 peak FLOP/s by device kind; NaN when unknown (CPU,
    unrecognized kinds) — callers omit MFU then. Delegates to the ONE
    spec table in ``horovod_tpu.tools.perf`` (shared with the live
    ``hvd_step_mfu_proxy`` gauge and the attribution records)."""
    from horovod_tpu.tools.perf import device_peak_flops
    device = device if device is not None else jax.devices()[0]
    return device_peak_flops(device)


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
                      return_rounds: bool = False, repeats: int = 1):
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

    ``repeats > 1`` times each (config, scan-length) cell that many times
    back-to-back within a round and keeps the min — a ROUND-LOCAL spike
    filter. Contention bursts on shared cores hit one repeat, not all
    three, so the per-round ratios (the band the guardrail states) tighten
    without sacrificing the round-local pairing that keeps drift shared
    (measured on scaling.py: per-arm ratio spread ~0.10-0.22 at repeats=1
    over a 6-arm group → ≤0.04 at repeats=3 over split groups).
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
                dt = float("inf")
                for _r in range(max(repeats, 1)):
                    t0 = time.perf_counter()
                    fn(k)
                    dt = min(dt, time.perf_counter() - t0)
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


def params_count(tree, select=None) -> int:
    """Total parameter count of a pytree; ``select(path_string) -> bool``
    filters leaves by their joined key path (lower-cased)."""
    import jax
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if select is not None:
            joined = "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                              for p in path).lower()
            if not select(joined):
                continue
        total += int(np.prod(leaf.shape)) if hasattr(leaf, "shape") else 0
    return total


def lm_train_flops_per_token(n_params_active: int, n_layers: int, dim: int,
                             seq: int) -> float:
    """Analytic training FLOPs per token for a decoder/encoder LM: the
    6·N parameter term (fwd 2N + bwd 4N, embeddings-in conventional) plus
    the attention-matmul term 12·L·T·d (QK^T and AV are 2·T·d FLOPs each
    fwd per layer-token, x3 for training) — the standard MFU accounting
    (PaLM appendix / scaling-book convention)."""
    return 6.0 * n_params_active + 12.0 * n_layers * seq * dim


def mfu_fields(per_chip_rate: float, flops_per_item: float) -> dict:
    """``{"mfu": ..., "peak_tflops": ...}`` for the JSON line, or {} when
    off-TPU / peak unknown (callers splat this into emit(**...))."""
    peak = peak_flops()
    if not on_tpu() or not np.isfinite(peak) or flops_per_item <= 0:
        return {}
    return {"mfu": round(per_chip_rate * flops_per_item / peak, 4),
            "peak_tflops": round(peak / 1e12, 1)}


def mixtral_bench_config(scan_layers: bool = False):
    """THE Mixtral TPU bench config — single source for mixtral.py,
    profile_mixtral.py and mixtral_opt_ab.py so the profiler's
    'exactly the bench config' contract cannot drift (r5 review).
    scan_layers=False since r5 (the unroll adoption); pass True to
    reproduce pre-r5 scan-variant measurements."""
    import jax.numpy as jnp
    from horovod_tpu.models.mixtral import MixtralConfig
    return MixtralConfig(vocab_size=32000, dim=512, n_layers=8,
                         n_heads=8, n_kv_heads=4, hidden_dim=1792,
                         n_experts=8, top_k=2, max_seq_len=1024,
                         use_flash=False, remat_policy="dots_attn",
                         scan_layers=scan_layers)
