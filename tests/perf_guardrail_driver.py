"""Subprocess driver for tests/test_perf_guardrail.py.

A minimal CPU-mesh ResNet profile -> step-time budget record
(docs/profiling.md). Runs in a FRESH process because per-op CPU trace
events need the thunk-runtime XLA flag armed before the backend
initializes (benchmarks/xprof.py::ensure_cpu_op_events) — the pytest
process initialized its backend long ago. Same record path as the big
benchmarks (`profiling_common` flops helper + `perf.attribute_logdir` +
`perf.append_history`), just on ResNetTiny so tier-1 stays fast; the
full ResNet-50 `profile_resnet.py` run is the slow-marked variant.
"""

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from profiling_common import compiled_step_flops, ensure_cpu_op_events  # noqa: E402

ensure_cpu_op_events()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

STEPS = 4


def main():
    import horovod_tpu as hvd
    from horovod_tpu.models import ResNetTiny
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.tools import perf
    from horovod_tpu.train import create_train_state, make_train_step

    hvd.init()
    batch = 8 * hvd.size()
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, 32, 32, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 100, size=(batch,)))

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    model = ResNetTiny(num_classes=100, dtype=jnp.float32,
                       axis_name=hvd.RANK_AXIS)
    dopt = distributed(optax.sgd(0.1, momentum=0.9),
                       axis_name=hvd.RANK_AXIS)
    state = create_train_state(model, jax.random.PRNGKey(0), images[:1],
                               dopt)
    step = make_train_step(model, dopt, loss_fn, mesh=hvd.mesh(),
                           axis_name=hvd.RANK_AXIS, donate=False)
    _, loss = step(state, images, labels)   # warm/compile outside trace
    np.asarray(loss)
    flops = compiled_step_flops(step, 1, state, images, labels)

    logdir = tempfile.mkdtemp(prefix="perf_guardrail_")
    with jax.profiler.trace(logdir):
        for _ in range(STEPS):
            _, loss = step(state, images, labels)
            np.asarray(loss)

    rec = perf.attribute_logdir(logdir, STEPS, model="resnet_tiny_cpu8",
                                metric="resnet_tiny_cpu_budget",
                                flops_per_step=flops)
    print(json.dumps(rec))
    path = perf.append_history(rec)
    if path:
        print(f"appended budget record to {path}")

    # Accumulation arm (ISSUE 12): same model, accum_steps=4 microbatch
    # loop — per-device batch 8 splits 4×2, grads accumulate in-graph,
    # ONE allreduce per step. Same budget shape + ratchet contract as the
    # plain arm, under its own model key.
    astep = make_train_step(model, dopt, loss_fn, mesh=hvd.mesh(),
                            axis_name=hvd.RANK_AXIS, donate=False,
                            accum_steps=4)
    _, loss = astep(state, images, labels)   # warm/compile outside trace
    np.asarray(loss)
    aflops = compiled_step_flops(astep, 1, state, images, labels)

    alogdir = tempfile.mkdtemp(prefix="perf_guardrail_accum_")
    with jax.profiler.trace(alogdir):
        for _ in range(STEPS):
            _, loss = astep(state, images, labels)
            np.asarray(loss)

    arec = perf.attribute_logdir(alogdir, STEPS,
                                 model="resnet_tiny_accum4_cpu8",
                                 metric="resnet_tiny_accum4_cpu_budget",
                                 flops_per_step=aflops)
    print(json.dumps(arec))
    apath = perf.append_history(arec)
    if apath:
        print(f"appended accum budget record to {apath}")


if __name__ == "__main__":
    main()
