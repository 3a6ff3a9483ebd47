"""Pipeline-parallel training over the ``pp`` mesh axis.

Capability-NEW vs the reference (SURVEY.md §2.6: "PP — absent"): each
device owns one stage of an MLP stack; activations hand off with
``lax.ppermute``; gradients flow back through either

- the **GPipe** schedule (``pipeline_value_and_grad`` — reverse-mode AD
  through the microbatch scan derives the backward pipeline from the
  ppermute transpose; O(microbatches) activation memory), or
- the **1F1B** schedule (``pipeline_1f1b_value_and_grad`` — hand-scheduled
  forward/backward interleave with an input ring + recompute-in-backward;
  O(stages) memory, the choice for many microbatches).

Both produce the sequential model's exact gradients (docs/long-context.md).

Run (single host, all local devices as stages):
    python examples/train_pipeline.py --steps 20
CPU smoke test (8 virtual devices = 8 stages):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_pipeline.py --steps 3 --microbatches 4
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
from jax.sharding import PartitionSpec as P

from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu.parallel import (create_mesh, pipeline_1f1b_value_and_grad,
                                  pipeline_value_and_grad)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--microbatch-size", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=8)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.5)
    p.add_argument("--schedule", choices=["gpipe", "1f1b"], default="1f1b")
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    mesh = create_mesh({"pp": n})
    D, M = args.dim, args.microbatches

    rng = np.random.RandomState(0)
    # One weight matrix per stage; stage r holds Ws[r].
    Ws = jnp.asarray(rng.randn(n, D, D).astype(np.float32) * 0.3)
    xs = jnp.asarray(rng.randn(M, args.microbatch_size, D)
                     .astype(np.float32))
    ts = jnp.asarray(rng.randn(M, args.microbatch_size, D)
                     .astype(np.float32))

    def stage_fn(W, x):
        return jnp.tanh(x @ W)

    if args.schedule == "1f1b":
        vg = pipeline_1f1b_value_and_grad(
            stage_fn, lambda y, t: jnp.mean((y - t) ** 2), "pp")
    else:
        vg = pipeline_value_and_grad(
            stage_fn, lambda outs, t: jnp.mean((outs - t) ** 2), "pp")

    def train_step(W, x, t):
        loss, g = vg(W[0], x, t)
        return (W[0] - args.lr * g)[None], loss[None]

    step = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(P("pp"), P(), P()),
        out_specs=(P("pp"), P("pp")), check_vma=False))

    if args.steps < 1:
        raise SystemExit("--steps must be >= 1")
    W, first, lv = Ws, None, None
    t0 = None
    for s in range(args.steps):
        W, loss = step(W, xs, ts)
        lv = float(np.asarray(loss)[0])
        if t0 is None:
            t0 = time.time()   # timer starts AFTER the compile-bearing step
        # loss is measured BEFORE the update this step applies, so even a
        # single step gives a meaningful first/last comparison next step.
        first = first if first is not None else lv
        if s % max(1, args.steps // 5) == 0:
            print(f"step {s:4d}  loss {lv:.5f}")
    rate = (args.steps - 1) / max(time.time() - t0, 1e-9)
    print(f"schedule={args.schedule} stages={n} microbatches={M} "
          f"loss={lv:.5f} (from {first:.5f}) "
          f"({rate:.1f} steps/s post-compile)")
    if args.steps > 1:
        assert lv < first, "pipeline training failed to reduce the loss"


if __name__ == "__main__":
    main()
