"""Llama fine-tuning over a dp×sp×tp GSPMD mesh (BASELINE config 3).

Reference analog: the reference's language-model scripts
(``examples/pytorch/pytorch_synthetic_benchmark.py`` pattern) are DP-only —
the model must fit one GPU. The TPU-native rebuild shards the model itself:
params carry logical axis names (``models/llama.py LOGICAL_RULES``), tokens
shard batch-over-dp and sequence-over-sp, and XLA inserts every collective
— including the DP gradient psum the reference needed its whole runtime
for (SURVEY.md §7 "architecture stance").

Run (single host, all local devices, axes auto-factored):
    python examples/train_llama.py --steps 20
CPU smoke test (8 virtual devices, dp2×sp2×tp2):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_llama.py --model tiny --dp 2 --sp 2 --tp 2 \
        --batch-size 4 --seq-len 64 --steps 3

Layer-loop trade (``LlamaConfig.scan_layers``): the default "auto" unrolls
small configs (n_layers ≤ 8 — this script's tiny model, fast compile AND
fast steps) and scans big ones (llama3_8b — bounded compile time). The
HEADLINE bench numbers (docs/benchmarks.md r5) run ``scan_layers=False``
(unrolled) even at 32 layers: +13% step throughput for ~3x compile time.
Pass an explicit True/False to pin the choice — it is checkpoint-visible
(scan stacks params under one "layers" node; unrolled uses block_i).
"""

import argparse
import time

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run in-repo without pip install

import jax
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.llama import (LOGICAL_RULES, Llama, llama3_8b,
                                      llama_tiny)
from horovod_tpu.parallel import create_mesh
from horovod_tpu.train import create_gspmd_train_state, make_gspmd_train_step

MODELS = {"llama3-8b": llama3_8b, "tiny": llama_tiny}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny", choices=MODELS)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel axis size (0 = all devices)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel axis size")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel axis size")
    p.add_argument("--batch-size", type=int, default=8,
                   help="global batch (sequences per step)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--attention-impl", choices=["auto", "ring", "ulysses"],
                   default="auto",
                   help="context-parallel attention over the sp axis "
                        "(docs/long-context.md); auto = dense/flash")
    p.add_argument("--remat-policy",
                   choices=["default", "full", "dots", "dots_attn", "attn"],
                   default="default",
                   help="checkpoint policy; attn/dots_attn save the "
                        "flash residuals so the backward skips the "
                        "fwd-kernel re-run (docs/benchmarks.md, remat "
                        "section)")
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    dp = args.dp or max(1, n // (args.sp * args.tp))
    if dp * args.sp * args.tp != n:
        raise SystemExit(f"dp*sp*tp = {dp}*{args.sp}*{args.tp} != {n} devices")
    mesh = create_mesh({"dp": dp, "sp": args.sp, "tp": args.tp})

    import dataclasses
    cfg = MODELS[args.model]()
    if args.attention_impl != "auto":
        cfg = dataclasses.replace(cfg, attention_impl=args.attention_impl)
    if args.remat_policy != "default":
        cfg = dataclasses.replace(cfg, remat=True,
                                  remat_policy=args.remat_policy)
    model = Llama(cfg)
    opt = optax.adamw(args.lr, weight_decay=0.01)

    rng = np.random.RandomState(0)
    tokens = np.asarray(rng.randint(1, cfg.vocab_size,
                                    (args.batch_size, args.seq_len)))

    state = create_gspmd_train_state(model, opt, jax.random.PRNGKey(0),
                                     tokens, mesh, LOGICAL_RULES)
    step = make_gspmd_train_step(model, opt, mesh, LOGICAL_RULES,
                                 data_axes=("dp",), seq_axis="sp")

    print(f"mesh dp={dp} sp={args.sp} tp={args.tp} "
          f"platform={jax.devices()[0].platform} model={args.model}")
    for _ in range(args.warmup):
        state, loss = step(state, tokens)
    if args.warmup:
        float(np.asarray(loss))  # sync
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = step(state, tokens)
    final_loss = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    tps = args.batch_size * args.seq_len * args.steps / dt
    print(f"loss={final_loss:.4f} tokens/sec={tps:.0f} "
          f"tokens/sec/chip={tps / n:.0f} step_ms={dt / args.steps * 1e3:.1f}")


if __name__ == "__main__":
    main()
