"""BERT MLM pretraining with compressed, fused gradient allreduce
(BASELINE config 2).

Reference analog: BERT-Large is the reference's bandwidth-bound headline —
fp16 wire compression (``hvd.Compression.fp16``) + tensor-fusion allreduce
of ~400 gradient tensors (SURVEY.md §6, docs/tensor-fusion.md). Here the
gradient pytree is flattened into ONE fused buffer inside the compiled
step (``grouped_allreduce``) with the compression cast fused in by XLA —
the same recipe with the memcpy staging deleted.

Run (single host, all local devices):
    python examples/train_bert.py --steps 20
CPU smoke test (8 virtual devices):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_bert.py --model tiny --batch-size 16 \
        --seq-len 32 --steps 3
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
from horovod_tpu.models.bert import Bert, bert_base, bert_large, bert_tiny
from horovod_tpu.optimizer import distributed
from horovod_tpu.train import create_train_state, make_train_step

MODELS = {"bert-large": bert_large, "bert-base": bert_base,
          "tiny": bert_tiny}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="bert-large", choices=MODELS)
    p.add_argument("--batch-size", type=int, default=64,
                   help="global batch size (split across devices)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--compression", choices=["none", "fp16", "bf16"],
                   default="fp16")
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    if args.batch_size % n:
        raise SystemExit(f"--batch-size must be divisible by {n} devices")

    cfg = MODELS[args.model]()
    seq = min(args.seq_len, cfg.max_seq_len)
    model = Bert(cfg)
    compression = {"none": hvd.Compression.none,
                   "fp16": hvd.Compression.fp16,
                   "bf16": hvd.Compression.bf16}[args.compression]
    dopt = distributed(optax.adamw(args.lr), compression=compression)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                     (args.batch_size, seq)))
    raw = rng.randint(0, cfg.vocab_size, (args.batch_size, seq))
    mask = rng.rand(args.batch_size, seq) < args.mask_prob
    labels = jnp.asarray(np.where(mask, raw, -1))  # -1 = unmasked position

    def loss_fn(logits, y):
        valid = y >= 0
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.maximum(y, 0))
        return (ce * valid).sum() / jnp.maximum(valid.sum(), 1)

    state = create_train_state(model, jax.random.PRNGKey(0), tokens[:1],
                               dopt)
    step = make_train_step(model, dopt, loss_fn)

    print(f"devices={n} platform={jax.devices()[0].platform} "
          f"model={args.model} seq={seq} compression={args.compression}")
    for _ in range(args.warmup):
        state, loss = step(state, tokens, labels)
    if args.warmup:
        float(np.asarray(loss))  # sync
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = step(state, tokens, labels)
    final_loss = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    tps = args.batch_size * seq * args.steps / dt
    print(f"loss={final_loss:.4f} tokens/sec={tps:.0f} "
          f"tokens/sec/chip={tps / n:.0f} step_ms={dt / args.steps * 1e3:.1f}")


if __name__ == "__main__":
    main()
