"""BASELINE config 1: ResNet-50 DP throughput + scaling efficiency.

Same measurement as the headline bench.py (slope-timed device-side scan)
plus the reference's own headline metric: scaling efficiency = per-chip
throughput with the full mesh active ÷ plain single-device throughput
(`docs/benchmarks.rst` reports this at 512 GPUs; here it is exact on
whatever mesh is present).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from common import (emit, median_ratio, on_tpu, slope_time_paired,
                    sync, S_SHORT, S_LONG)


def main():
    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50, ResNetTiny
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    hvd.init()
    n = hvd.size()
    tpu = on_tpu()
    per_chip, image = (64, 224) if tpu else (4, 32)
    model_cls = ResNet50 if tpu else ResNetTiny
    batch = per_chip * n

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, image, image, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 1000, size=(batch,)))

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    model = model_cls(axis_name=hvd.RANK_AXIS,
                      dtype=jnp.bfloat16 if tpu else jnp.float32)
    dopt = distributed(optax.sgd(0.1, momentum=0.9))
    state = create_train_state(model, jax.random.PRNGKey(0), images[:1],
                               dopt)
    steps = {k: make_train_step(model, dopt, loss_fn, scan_steps=k,
                                donate=False)
             for k in (S_SHORT, S_LONG)}

    def run(k):
        _, loss = steps[k](state, images, labels)
        sync(loss)

    # Single-device plain baseline for scaling efficiency, through the SAME
    # harness (bench.py methodology: interleaved rounds so drift cannot
    # land on one side of the ratio; see common.slope_time_paired).
    model1 = model_cls(axis_name=None,
                       dtype=jnp.bfloat16 if tpu else jnp.float32)
    opt1 = optax.sgd(0.1, momentum=0.9)
    x1, y1 = images[:per_chip], labels[:per_chip]
    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]),
                              (hvd.RANK_AXIS,))
    pstate = create_train_state(model1, jax.random.PRNGKey(0), x1[:1], opt1,
                                broadcast=False, mesh=mesh1)
    plains = {k: make_train_step(model1, opt1, loss_fn, scan_steps=k,
                                 mesh=mesh1, donate=False)
              for k in (S_SHORT, S_LONG)}

    def run1(k):
        _, loss = plains[k](pstate, x1, y1)
        sync(loss)

    sec, rounds = slope_time_paired({"hvd": run, "plain": run1},
                                    return_rounds=True)
    ips = batch / sec["hvd"]
    # Median of round-local ratios: robust to contended bursts (see
    # common.median_ratio).
    eff = median_ratio(rounds, "plain", "hvd")
    emit("resnet50_images_per_sec_per_chip", ips / n,
         f"images/sec/chip (batch {per_chip}/chip, {n} devices)")
    emit("resnet50_scaling_efficiency", eff,
         f"per-chip throughput vs 1-device plain JAX ({n} devices)", eff)


if __name__ == "__main__":
    main()
