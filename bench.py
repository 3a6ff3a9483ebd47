"""Headline benchmark — run by the driver on real TPU hardware.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric: ResNet-50 synthetic-data training throughput (images/sec/chip) with
the FULL horovod_tpu distributed machinery active (in-graph fused gradient
allreduce via DistributedOptimizer over the device mesh) — BASELINE.md
config 1. ``vs_baseline`` is the throughput ratio against a plain-JAX train
step with no distributed wrapper, measured identically in the same run: the
reference's headline number is scaling efficiency (~0.90 for ResNet at 512
GPUs); on one chip the honest equivalent is distributed-machinery overhead
(>= 1.0 means the in-graph collective design costs nothing), and on a
multi-chip mesh this becomes per-chip scaling efficiency.

Timing method: the step loop runs DEVICE-SIDE via lax.scan (one dispatch);
wall time is taken as the slope between a short and a long scan with a
device->host sync after each, which cancels the constant dispatch and
transfer latency. Whether a local chip still needs this is ROADMAP S0's
call: chip_smoke.py prints the per-step ``block_until_ready`` time and this
slope side by side for the same model.

Needs a TPU: without one it exits non-zero and prints no result.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))
from common import median_ratio, peak_flops, slope_time_paired

S_SHORT, S_LONG = 4, 24

# Analytic training-FLOPs model for ResNet-50 at 224x224: 4.089 GMACs
# forward (standard count) x 2 FLOPs/MAC x 3 (fwd + bwd ~ 2x fwd).
# XLA's cost_analysis is NOT usable here: on the TPU backend it reports
# ~1.49 GFLOP/img for this model (convs under-counted ~16x; measured via
# benchmarks/mfu_probe.py), so MFU uses the analytic constant.
RESNET50_TRAIN_FLOPS_PER_IMG = 4.089e9 * 2 * 3


def _sync(x):
    return np.asarray(jax.tree_util.tree_leaves(x)[0]).ravel()[0]


def main():
    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    dev = jax.devices()[0]
    platform = dev.platform
    if platform != "tpu":
        sys.exit(f"bench.py measures on a TPU; jax found platform "
                 f"{platform!r} ({dev.device_kind})")
    peak = peak_flops(dev)
    if not np.isfinite(peak):
        # an unknown chip is an error, not an MFU silently left out
        sys.exit(f"bench.py: no peak FLOP/s on record for device kind "
                 f"{dev.device_kind!r} (tools/perf.py _PEAK_TABLE)")
    hvd.init()
    n = hvd.size()
    # Per-chip batch 128: bigger batches amortize BN/elementwise HBM passes
    # over more MXU work; 256 doubles activation memory.
    per_chip_batch = 128
    image = 224
    batch = per_chip_batch * n

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, image, image, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, size=(batch,)))

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    # One factory so the hvd and plain sides can never diverge in anything
    # but axis_name. Space-to-depth stem: the standard TPU stem rework — 12
    # input channels instead of 3, so the stem conv stops wasting MXU input
    # lanes.
    def mk_model(axis_name):
        return ResNet50(axis_name=axis_name, dtype=jnp.bfloat16,
                        stem="space_to_depth")
    model = mk_model(hvd.RANK_AXIS)

    # --- horovod_tpu DP path (the product) ---
    dopt = distributed(optax.sgd(0.1, momentum=0.9))
    state0 = create_train_state(model, jax.random.PRNGKey(0), images[:1],
                                dopt)
    steps = {k: make_train_step(model, dopt, loss_fn, scan_steps=k,
                                donate=False)
             for k in (S_SHORT, S_LONG)}

    def run_hvd(k):
        _, loss = steps[k](state0, images, labels)
        _sync(loss)

    # --- plain-JAX baseline: no distributed wrapper, no BN sync, no mesh,
    # through the SAME train-step harness so the ratio isolates exactly the
    # distributed machinery (harness-structure differences measured as a
    # phantom 2-4% before).
    model_plain = mk_model(None)
    popt = optax.sgd(0.1, momentum=0.9)
    mesh1 = jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]), (hvd.RANK_AXIS,))
    pstate0 = create_train_state(model_plain, jax.random.PRNGKey(0),
                                 images[:1], popt, broadcast=False,
                                 mesh=mesh1)
    x1 = images[:per_chip_batch]
    y1 = labels[:per_chip_batch]
    psteps = {k: make_train_step(model_plain, popt, loss_fn, scan_steps=k,
                                 mesh=mesh1, donate=False)
              for k in (S_SHORT, S_LONG)}

    def run_plain(k):
        _, loss = psteps[k](pstate0, x1, y1)
        _sync(loss)

    # Interleave the two configs so device drift cannot land on one side of
    # the ratio; the ratio is the MEDIAN of round-local ratios, which stays
    # honest even when a contended burst hits part of the run.
    sec, rounds = slope_time_paired({"hvd": run_hvd, "plain": run_plain},
                                    S_SHORT, S_LONG, return_rounds=True)
    ips_hvd = batch / sec["hvd"]
    vs_baseline = median_ratio(rounds, "plain", "hvd")

    per_chip = ips_hvd / n
    record = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": f"images/sec/chip (bf16, s2d stem, batch "
                f"{per_chip_batch}/chip, {n}x{platform})",
        "vs_baseline": round(vs_baseline, 4),
        # The spread of THIS run's interleaved round-local ratios.
        "vs_baseline_rounds": {
            "rounds": len([r for r in rounds
                           if r.get("plain", 0) > 2e-9
                           and r.get("hvd", 0) > 2e-9]),
            "ratio_min": round(min((r["plain"] / r["hvd"] for r in rounds
                                    if r.get("plain", 0) > 2e-9
                                    and r.get("hvd", 0) > 2e-9),
                                   default=float("nan")), 4),
            "ratio_max": round(max((r["plain"] / r["hvd"] for r in rounds
                                    if r.get("plain", 0) > 2e-9
                                    and r.get("hvd", 0) > 2e-9),
                                   default=float("nan")), 4),
        },
    }
    # Model FLOP utilization against the chip's bf16 peak (analytic FLOPs
    # model; see RESNET50_TRAIN_FLOPS_PER_IMG).
    record["mfu"] = round(per_chip * RESNET50_TRAIN_FLOPS_PER_IMG / peak, 4)
    record["peak_tflops"] = round(peak / 1e12, 1)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
