"""DLRM training with sharded embedding tables (BASELINE config 5).

Reference analog: the reference's DLRM story is sparse allgather/allreduce
of embedding gradients over DP workers (SURVEY.md §6). TPU-native, the
embedding tables themselves shard over the ``ep`` mesh axis and XLA inserts
the gather/exchange from the sharding annotations — the lookup rides ICI
instead of every worker holding (and reducing) full tables.

Run (single host, all local devices):
    python examples/train_dlrm.py --steps 20
CPU smoke test (8 virtual devices, dp2×ep4):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_dlrm.py --model tiny --dp 2 --ep 4 \
        --batch-size 64 --steps 3
"""

import argparse
import time

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run in-repo without pip install


import flax.linen as nn
from flax.linen import partitioning as nn_partitioning
import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.dlrm import DLRM, bce_loss, dlrm_criteo, dlrm_tiny
from horovod_tpu.models.llama import LOGICAL_RULES
from horovod_tpu.parallel import create_mesh
from horovod_tpu.train import rules_for_mesh

MODELS = {"criteo": dlrm_criteo, "tiny": dlrm_tiny}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="criteo", choices=MODELS)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel axis size (0 = devices // ep)")
    p.add_argument("--ep", type=int, default=0,
                   help="embedding-shard axis size (0 = min(8, devices))")
    p.add_argument("--batch-size", type=int, default=2048,
                   help="global batch size")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--sparse-embeddings", action="store_true",
                   help="sparse row-Adagrad for the tables (the "
                        "reference's sparse-gradient DLRM semantics; "
                        "numerically identical to dense Adagrad, ~2x "
                        "faster at the criteo config — see "
                        "docs/benchmarks.md r4)")
    args = p.parse_args()

    hvd.init()
    n = hvd.size()
    ep = args.ep or min(8, n)
    dp = args.dp or max(1, n // ep)
    if dp * ep != n:
        raise SystemExit(f"dp*ep = {dp}*{ep} != {n} devices")
    mesh = create_mesh({"dp": dp, "ep": ep})
    rules = rules_for_mesh(mesh, LOGICAL_RULES)

    cfg = MODELS[args.model]()
    model = DLRM(cfg)

    rng = np.random.RandomState(0)
    dense = jnp.asarray(rng.randn(args.batch_size, cfg.dense_features)
                        .astype(np.float32))
    sparse = jnp.asarray(rng.randint(0, cfg.rows_per_table,
                                     (args.batch_size, cfg.num_tables)))
    labels = jnp.asarray((rng.rand(args.batch_size) < 0.3)
                         .astype(np.float32))

    with nn_partitioning.axis_rules(rules):
        abs_vars = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                  dense, sparse)
    sharding = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abs_vars["params"]), mesh, rules)

    def init_all(rng_):
        with nn_partitioning.axis_rules(rules):
            return model.init(rng_, dense, sparse)["params"]

    with jax.sharding.set_mesh(mesh):
        params = jax.jit(init_all, out_shardings=sharding)(
            jax.random.PRNGKey(0))
    params = nn.meta.unbox(params)

    if args.sparse_embeddings:
        # the SHARED setup (pinned row-major table layouts + donation) —
        # hand-rolling this path loses ~2x to XLA's entry-layout
        # transposes (docs/benchmarks.md r4 DLRM section)
        from horovod_tpu.models.dlrm import build_sparse_training
        sparse_step, dense_params, tables, accum, opt_state = \
            build_sparse_training(model, cfg, mesh, rules, params,
                                  lr=args.lr)
        state = [dense_params, tables, accum, opt_state]

        def run_one(d, s, y):
            out = sparse_step(state[0], state[1], state[2], state[3],
                              d, s, y)
            state[:] = out[:4]
            return out[4]
    else:
        opt = optax.adagrad(args.lr)
        opt_state = opt.init(params)

        def step(params, opt_state, d, s, y):
            def loss_of(p):
                with nn_partitioning.axis_rules(rules):
                    out = model.apply({"params": p}, d, s)
                return bce_loss(out, y)
            loss, grads = jax.value_and_grad(loss_of)(params)
            updates, opt_state2 = opt.update(grads, opt_state, params)
            return optax.apply_updates(  # hvd-analyze: ok — demo loop
                params, updates), opt_state2, loss

        jitted = jax.jit(step, donate_argnums=(0, 1))
        state = [params, opt_state]

        def run_one(d, s, y):
            out = jitted(state[0], state[1], d, s, y)
            state[:] = out[:2]
            return out[2]

    print(f"mesh dp={dp} ep={ep} tables={cfg.num_tables}x"
          f"{cfg.rows_per_table} platform={jax.devices()[0].platform}")
    with jax.sharding.set_mesh(mesh):
        loss = None
        for _ in range(args.warmup):
            loss = run_one(dense, sparse, labels)
        if args.warmup:
            float(np.asarray(loss))
        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss = run_one(dense, sparse, labels)
        final_loss = float(np.asarray(loss))
    dt = time.perf_counter() - t0
    eps = args.batch_size * args.steps / dt
    print(f"loss={final_loss:.4f} examples/sec={eps:.0f} "
          f"examples/sec/chip={eps / n:.0f} "
          f"step_ms={dt / args.steps * 1e3:.1f}")


if __name__ == "__main__":
    main()
