"""Elastic training example (reference: examples/elastic/pytorch synced to
SURVEY.md §3.4's loop shape).

Run fault-tolerant on a dynamic host set:

    python -m horovod_tpu.runner --min-np 1 --max-np 8 \
        --host-discovery-script ./discover_hosts.sh \
        python examples/train_elastic.py

The wrapper + driver handle worker crashes (rollback to the last commit)
and membership changes (graceful generation restart with state carried via
persisted commits).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run in-repo without pip install

import horovod_tpu as hvd
from horovod_tpu import elastic
from horovod_tpu.models import ResNetTiny
from horovod_tpu.optimizer import distributed
from horovod_tpu.train import create_train_state, make_train_step

EPOCHS = 3
STEPS_PER_EPOCH = 8
BATCH_PER_RANK = 8


def main():
    hvd.init()
    model = ResNetTiny(num_classes=10, axis_name=hvd.RANK_AXIS)
    opt = distributed(optax.sgd(0.05, momentum=0.9))

    rng = np.random.RandomState(0)
    images = jnp.asarray(
        rng.randn(BATCH_PER_RANK * hvd.size(), 8, 8, 3).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=(images.shape[0],)))

    tstate = create_train_state(model, jax.random.PRNGKey(0), images[:1], opt)
    step = make_train_step(
        model, opt,
        lambda logits, y: optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean())

    state = elastic.JaxState(params=tstate.params,
                             opt_state=tstate.opt_state,
                             epoch=0, batch=0)

    @elastic.run
    def train(state):
        nonlocal tstate
        # Adopt (possibly restored/synced) state into the train loop.
        tstate = tstate._replace(params=jax.device_put(state.params),
                                 opt_state=jax.device_put(state.opt_state))
        while state.epoch < EPOCHS:
            while state.batch < STEPS_PER_EPOCH:
                tstate, loss = step(tstate, images, labels)
                state.batch += 1
                state.params = tstate.params
                state.opt_state = tstate.opt_state
                state.commit()
            if hvd.cross_rank() == 0:
                print(f"epoch {state.epoch} done, loss={float(loss):.4f}")
            state.epoch += 1
            state.batch = 0
            state.commit()
        return float(loss)

    final = train(state)
    if hvd.cross_rank() == 0:
        print(f"final loss {final:.4f}")


if __name__ == "__main__":
    main()
