"""Mixtral MoE training over a dp×ep mesh (BASELINE config 4).

Reference analog: the reference only ships the ``hvd.alltoall`` primitive
an MoE layer would need (SURVEY.md §2.6 — "no MoE layer/router anywhere").
Here the full path exists: top-2 router → expert dispatch over the ``ep``
mesh axis (``parallel/moe.py``) with the token exchange riding ICI, plus
the router load-balancing auxiliary loss.

Run (single host, all local devices):
    python examples/train_mixtral.py --steps 20
CPU smoke test (8 virtual devices, dp2×ep4):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_mixtral.py --dp 2 --ep 4 --batch-size 4 \
        --seq-len 64 --steps 3

Layer-loop trade (``MixtralConfig.scan_layers``, inherited from
LlamaConfig): the default "auto" unrolls small configs (n_layers ≤ 8 —
this script's tiny model) and scans big ones (mixtral_8x7b). The HEADLINE
bench numbers (docs/benchmarks.md r5) run ``scan_layers=False`` even at
32 layers: +22% Mixtral step throughput for ~3x compile time. Pin an
explicit True/False for runs whose checkpoints must survive config edits
(the param tree differs between the two layouts).
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
from horovod_tpu.models.llama import LOGICAL_RULES
from horovod_tpu.models.mixtral import Mixtral, mixtral_8x7b, mixtral_tiny
from horovod_tpu.parallel import create_mesh
from horovod_tpu.train import create_gspmd_train_state, make_gspmd_train_step

MODELS = {"mixtral-8x7b": mixtral_8x7b, "tiny": mixtral_tiny}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny", choices=MODELS)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel axis size (0 = devices // ep)")
    p.add_argument("--ep", type=int, default=0,
                   help="expert-parallel axis size (0 = min(8, devices))")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    ep = args.ep or min(8, n)
    dp = args.dp or max(1, n // ep)
    if dp * ep != n:
        raise SystemExit(f"dp*ep = {dp}*{ep} != {n} devices")
    mesh = create_mesh({"dp": dp, "ep": ep})

    cfg = MODELS[args.model]()
    model = Mixtral(cfg)
    opt = optax.adamw(args.lr, weight_decay=0.01)

    rng = np.random.RandomState(0)
    tokens = np.asarray(rng.randint(1, cfg.vocab_size,
                                    (args.batch_size, args.seq_len)))

    state = create_gspmd_train_state(model, opt, jax.random.PRNGKey(0),
                                     tokens, mesh, LOGICAL_RULES)
    step = make_gspmd_train_step(model, opt, mesh, LOGICAL_RULES,
                                 data_axes=("dp",),
                                 aux_weight=cfg.router_aux_weight)

    print(f"mesh dp={dp} ep={ep} experts={cfg.n_experts} "
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
