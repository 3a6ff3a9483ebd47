"""Llama fine-tuning with Adasum gradient combination (BASELINE config 3).

Reference analog: ``hvd.DistributedOptimizer(..., op=hvd.Adasum)`` — the
scale-invariant pairwise gradient combine (``ops/adasum/adasum.h``,
SURVEY.md §2.2) that lets batch size scale without LR retuning. Here the
recursive-halving tree is an XOR butterfly of ``ppermute`` partner
exchanges over the ICI ring (``collectives/adasum.py``), with the
dot/norm/combine math in a fused Pallas kernel, running INSIDE the
compiled train step.

Run (single host, all local devices):
    python examples/train_adasum.py --steps 20
CPU smoke test (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_adasum.py --batch-size 8 --seq-len 64 \
        --steps 3
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
from horovod_tpu.models.llama import Llama, llama3_8b, llama_tiny
from horovod_tpu.optimizer import distributed
from horovod_tpu.train import (create_train_state, make_train_step,
                               next_token_loss)

MODELS = {"llama3-8b": llama3_8b, "tiny": llama_tiny}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny", choices=MODELS)
    p.add_argument("--batch-size", type=int, default=8,
                   help="global batch (sequences per step)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--op", choices=["adasum", "average"], default="adasum")
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    if args.batch_size % n:
        raise SystemExit(f"--batch-size must be divisible by {n} devices")

    cfg = MODELS[args.model]()
    model = Llama(cfg)
    op = hvd.Adasum if args.op == "adasum" else hvd.Average
    dopt = distributed(optax.adamw(args.lr), op=op)

    rng = np.random.RandomState(0)
    seq = min(args.seq_len, cfg.max_seq_len)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab_size,
                                     (args.batch_size, seq)))

    def loss_fn(logits, toks):
        return next_token_loss(logits, toks)

    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1],
                               dopt)
    step = make_train_step(model, dopt, loss_fn)

    print(f"devices={n} platform={jax.devices()[0].platform} "
          f"model={args.model} op={args.op}")
    for _ in range(args.warmup):
        state, loss = step(state, tokens, tokens)
    if args.warmup:
        float(np.asarray(loss))  # sync
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = step(state, tokens, tokens)
    final_loss = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    tps = args.batch_size * seq * args.steps / dt
    print(f"loss={final_loss:.4f} tokens/sec={tps:.0f} "
          f"tokens/sec/chip={tps / n:.0f} step_ms={dt / args.steps * 1e3:.1f}")


if __name__ == "__main__":
    main()
