"""ResNet ImageNet-style DP training example.

Parity with the reference's flagship example
(``examples/pytorch/pytorch_imagenet_resnet50.py`` /
``tensorflow2_synthetic_benchmark.py``): init → broadcast params → per-step
fwd/bwd with in-graph gradient allreduce → optimizer update, reporting
images/sec. Synthetic data by default (like the reference's synthetic
benchmark) so it runs anywhere.

Run (single host, all local devices):
    python examples/train_resnet.py --batch-size 128 --steps 100
CPU smoke test (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_resnet.py --model tiny --image-size 32 \
        --batch-size 16 --steps 5
"""

import argparse
import time

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run in-repo without pip install

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import ResNet50, ResNet18, ResNetTiny
from horovod_tpu.optimizer import distributed
from horovod_tpu.train import create_train_state, make_train_step

MODELS = {"resnet50": ResNet50, "resnet18": ResNet18, "tiny": ResNetTiny}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50", choices=MODELS)
    p.add_argument("--batch-size", type=int, default=128,
                   help="global batch size (split across devices)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--bf16", action="store_true", default=True)
    p.add_argument("--compression", choices=["none", "fp16", "bf16"],
                   default="none")
    p.add_argument("--backward-passes-per-step", type=int, default=1)
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    if args.batch_size % n:
        raise SystemExit(f"--batch-size must be divisible by {n} devices")

    model_kwargs = dict(num_classes=args.num_classes,
                        axis_name=hvd.RANK_AXIS)
    if args.model != "tiny":
        model_kwargs["dtype"] = jnp.bfloat16 if args.bf16 else jnp.float32
    model = MODELS[args.model](**model_kwargs)

    compression = {"none": hvd.Compression.none,
                   "fp16": hvd.Compression.fp16,
                   "bf16": hvd.Compression.bf16}[args.compression]
    opt = distributed(
        optax.sgd(args.lr, momentum=0.9),
        compression=compression,
        backward_passes_per_step=args.backward_passes_per_step)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(
        args.batch_size, args.image_size, args.image_size, 3)
        .astype(np.float32))
    labels = jnp.asarray(rng.randint(0, args.num_classes,
                                     size=(args.batch_size,)))

    state = create_train_state(model, jax.random.PRNGKey(0), images[:1], opt)
    step = make_train_step(model, opt, loss_fn)

    print(f"devices={n} platform={jax.devices()[0].platform} "
          f"global_batch={args.batch_size} model={args.model}")
    for i in range(args.warmup):
        state, loss = step(state, images, labels)
    if args.warmup:
        float(loss)  # value fetch: waits for the device
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, loss = step(state, images, labels)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    ips = args.batch_size * args.steps / dt
    print(f"loss={final_loss:.4f} images/sec={ips:.1f} "
          f"images/sec/chip={ips / n:.1f} step_ms={dt / args.steps * 1e3:.2f}")


if __name__ == "__main__":
    main()
