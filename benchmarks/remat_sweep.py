"""Compute-tier remat × scan sweep: every (remat policy, scan/unroll) arm
of one model, interleaved, each arm recorded into the perf ratchet.

Generalizes ``llama_remat_ab.py`` (which A/Bs exactly two policies at the
TPU bench shape) into the tuning pass the compute tier runs per model:

- arms are the cross product of remat policies (``models/llama.py::
  with_remat_policy`` vocabulary) and the scan-vs-unroll layer choice —
  the two knobs that decide what the backward recomputes and what the
  loop-carried gradient stacks cost;
- every arm is timed with ``slope_time_paired`` interleaved rounds
  (windows 2 and 8 — multiples of any apply cadence; none is engaged
  here), so run-to-run drift lands on every arm equally;
- every non-baseline arm appends ONE ``kind: "perf_ratio"`` record to
  ``benchmarks/perf_history.jsonl`` (its interleaved step-time ratio vs
  the "full"+scan baseline, higher = faster), so ``tools/perf check``
  rails the best measured configuration as a floor from then on.

Usage:  python benchmarks/remat_sweep.py            (CPU mesh or chip)
        HOROVOD_PERF_NO_HISTORY=1 ... to measure without ratcheting
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax

from common import emit, median_ratio, on_tpu, slope_time_paired, sync

#: (remat policy, scan_layers) — the arm every ratio is measured against.
BASELINE = ("full", True)


def _arm_name(policy: str, scan: bool) -> str:
    return f"remat_{policy}_{'scan' if scan else 'unroll'}"


def main():
    import horovod_tpu as hvd
    from horovod_tpu.models.llama import (Llama, LlamaConfig, llama_tiny,
                                          with_remat_policy)
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.tools import perf
    from horovod_tpu.train import (create_train_state, make_train_step,
                                   next_token_loss)

    hvd.init()
    n = hvd.size()
    if on_tpu():
        base = LlamaConfig(vocab_size=32000, dim=1024, n_layers=24,
                           n_heads=16, n_kv_heads=8, hidden_dim=4096,
                           max_seq_len=2048)
        # "none"/"dots" OOM at the bench batch (see llama_remat_ab.py);
        # the flash-residual family is the real TPU design space.
        policies, per_chip, seq = ("full", "attn", "dots_attn"), 8, 1024
        model_name = f"llama_remat_sweep_tpu{n}"
    else:
        # CPU mesh: 4 unrolled layers trace in seconds and full-remat
        # recompute is pure extra arithmetic — the none-vs-full arm is a
        # real, rail-able compute-tier win even without a chip. The shape
        # is widened past llama_tiny so matmul work dominates dispatch
        # overhead (at dim=64/seq=32 every arm reads ~50 ms of overhead
        # and the arms don't separate).
        base = dataclasses.replace(llama_tiny(), n_layers=4, dim=128,
                                   hidden_dim=512)
        policies, per_chip, seq = ("none", "full", "dots"), 2, 64
        model_name = f"llama_tiny_cpu{n}"
    batch = per_chip * n
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, base.vocab_size, (batch, seq)))
    dopt = distributed(optax.adamw(1e-4))

    def loss_fn(logits, y):
        return next_token_loss(logits, y)

    # ONE state per scan mode (scan stacks params [L,...] under a single
    # "layers" node, unrolled uses block_i — different pytrees); remat
    # policies share it. donate=False keeps the state reusable.
    states = {}
    runs = {}
    for scan in (True, False):
        for pol in policies:
            cfg = dataclasses.replace(with_remat_policy(base, pol),
                                      scan_layers=scan)
            model = Llama(cfg)
            if scan not in states:
                states[scan] = create_train_state(
                    model, jax.random.PRNGKey(0), tokens[:1], dopt)
            steps = {k: make_train_step(model, dopt, loss_fn,
                                        scan_steps=k, donate=False)
                     for k in (2, 8)}

            def run(k, _steps=steps, _state=states[scan]):
                _, loss = _steps[k](_state, tokens, tokens)
                sync(loss)

            runs[_arm_name(pol, scan)] = run

    secs, rounds = slope_time_paired(runs, 2, 8, return_rounds=True)
    base_arm = _arm_name(*BASELINE)
    for name in sorted(runs):
        if name == base_arm:
            continue
        ratio = median_ratio(rounds, base_arm, name)  # >1: arm is faster
        valid = [r[base_arm] / r[name] for r in rounds
                 if r[base_arm] > 2e-9 and r[name] > 2e-9]
        noise = {"lo": round(min(valid), 4),
                 "hi": round(max(valid), 4)} if valid else None
        record = {"kind": "perf_ratio",
                  "metric": f"{model_name}_{name}_step_ratio",
                  "model": model_name, "arm": name,
                  "ratio": round(float(ratio), 4), "baseline": base_arm,
                  "noise": noise, "seq": seq,
                  "batch_per_chip": per_chip, "devices": n,
                  "sec_per_step": round(secs[name], 6),
                  "baseline_sec_per_step": round(secs[base_arm], 6)}
        perf.append_history(record)
        emit(f"{model_name}_{name}_step_ratio", ratio,
             f"interleaved step-time ratio vs {base_arm} "
             f"(higher = this arm is faster)", **{
                 k: record[k] for k in ("noise", "sec_per_step",
                                        "baseline_sec_per_step")})


if __name__ == "__main__":
    main()
