"""Online DLRM: continuous training feeding a live inference server.

The serving-plane demo (docs/serving.md). One DLRM trains and commits
through the elastic CAS checkpoint path; ``serving.attach`` publishes
every Nth known-good commit; a serving process discovers publishes from
the shared commit dir (store-watch — no coordinator needed), delta-
fetches only changed blobs, and RCU-swaps the served params with zero
dropped requests. Requests are dynamically batched into bucketed shapes
so the jitted forward never recompiles on the request path.

Run the two halves in separate shells against a shared directory:
    python examples/online_dlrm.py train --commit-dir /tmp/dlrm_pub
    python examples/online_dlrm.py serve --commit-dir /tmp/dlrm_pub
or the single-process smoke:
    JAX_PLATFORMS=cpu python examples/online_dlrm.py demo
"""

import argparse
import json
import tempfile
import threading
import time
import urllib.request

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))  # run in-repo without pip install

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu import serving
from horovod_tpu.elastic.state import ObjectState
from horovod_tpu.models.dlrm import DLRM, bce_loss, dlrm_tiny


def _model():
    return DLRM(dlrm_tiny()), dlrm_tiny()


def _batch(cfg, rng, n):
    dense = rng.randn(n, cfg.dense_features).astype(np.float32)
    sparse = rng.randint(0, cfg.rows_per_table, (n, cfg.num_tables))
    labels = (rng.rand(n) < 0.3).astype(np.float32)
    return dense, sparse, labels


def train(args):
    """Train + commit; serving.attach publishes every Nth clean commit."""
    model, cfg = _model()
    rng = np.random.RandomState(0)
    dense, sparse, labels = _batch(cfg, rng, args.batch_size)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(dense),
                        jnp.asarray(sparse))["params"]
    opt = optax.adagrad(args.lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, d, s, y):
        def loss_of(p):
            return bce_loss(model.apply({"params": p}, d, s), y)
        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(  # hvd-analyze: ok — demo loop
            params, updates), opt_state, loss

    state = ObjectState(commit_dir=args.commit_dir, params=params,
                        opt_state=opt_state, step=0)
    pub = serving.attach(args.commit_dir, every=args.publish_every)
    try:
        for i in range(1, args.steps + 1):
            d, s, y = _batch(cfg, rng, args.batch_size)
            state.params, state.opt_state, loss = step(
                state.params, state.opt_state, jnp.asarray(d),
                jnp.asarray(s), jnp.asarray(y))
            state.step = i
            if i % args.commit_every == 0:
                state.commit()   # -> CAS blobs + publish gate via attach
                print(json.dumps({
                    "step": i, "loss": round(float(loss), 4),
                    "committed_seq": state._commit_seq,
                    "published": (pub.last_published or {}).get(
                        "manifest_seq")}), flush=True)
                if args.step_s:
                    time.sleep(args.step_s)
        state.flush_commits(timeout=60)
    finally:
        serving.detach(pub)


def build_forward(model, cfg):
    """Request dicts -> padded device batch -> jitted apply -> floats.

    Compiles once per bucket shape (HOROVOD_SERVING_BUCKETS), never per
    request: the batcher hands over ``padded_n`` already snapped to a
    bucket, and the pad rows are sliced off after the forward.
    """
    @jax.jit
    def apply(params, dense, sparse):
        return model.apply({"params": params}, dense, sparse, train=False)

    def forward(payload, inputs, padded_n):
        dense = np.zeros((padded_n, cfg.dense_features), dtype=np.float32)
        sparse = np.zeros((padded_n, cfg.num_tables), dtype=np.int32)
        for i, q in enumerate(inputs):
            dense[i] = np.asarray(q["dense"], dtype=np.float32)
            sparse[i] = np.asarray(q["sparse"], dtype=np.int32)
        scores = apply(payload["attrs"]["params"], jnp.asarray(dense),
                       jnp.asarray(sparse))
        return [float(s) for s in np.asarray(scores)[:len(inputs)]]

    return forward


def serve(args, stop=None):
    """Store-watch serving: poll the shared commit dir for publish pins,
    hot-swap on each new generation, answer /predict."""
    model, cfg = _model()
    # prepare_leaf puts each fetched blob on device ONCE; unchanged
    # leaves are then reused across swaps as live device arrays.
    reg = serving.ModelRegistry(prepare_leaf=jnp.asarray)
    srv = serving.InferenceServer(reg, build_forward(model, cfg),
                                  bind_host=args.host)
    srv.start_watch(store=serving.Publisher(
        args.commit_dir, every=1).store, poll_s=args.poll_s)
    print(json.dumps({"serving": srv.addr()}), flush=True)
    try:
        while stop is None or not stop.is_set():
            time.sleep(0.2)
    except KeyboardInterrupt:
        pass
    finally:
        srv.close()
    return srv


def demo(args):
    """Single-process smoke: trainer thread + server + a client."""
    args.commit_dir = args.commit_dir or tempfile.mkdtemp(
        prefix="hvd_online_dlrm_")
    args.steps, args.commit_every, args.step_s = 6, 2, 0.3
    _, cfg = _model()
    trainer = threading.Thread(target=train, args=(args,), daemon=True)
    trainer.start()
    stop = threading.Event()
    model, cfg = _model()
    reg = serving.ModelRegistry(prepare_leaf=jnp.asarray)
    srv = serving.InferenceServer(reg, build_forward(model, cfg))
    srv.start_watch(store=serving.Publisher(
        args.commit_dir, every=1).store, poll_s=0.1)
    rng = np.random.RandomState(1)
    answered = 0
    try:
        deadline = time.time() + 60
        while trainer.is_alive() and time.time() < deadline:
            if reg.current() is None:
                time.sleep(0.1)
                continue
            d, s, _ = _batch(cfg, rng, 1)
            body = json.dumps({"dense": d[0].tolist(),
                               "sparse": s[0].tolist()}).encode()
            req = urllib.request.Request(
                f"http://{srv.addr()}/predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                out = json.loads(r.read())
            assert out["ok"], out
            answered += 1
            time.sleep(0.05)
        trainer.join(timeout=60)
    finally:
        stop.set()
        srv.close()
    print(json.dumps({"demo_ok": answered > 0, "answered": answered,
                      "final_model_seq": getattr(reg.current(),
                                                 "manifest_seq", None),
                      "swaps": reg.stats["swaps"],
                      "leaves_reused": reg.stats["leaves_reused"]}),
          flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("mode", choices=("train", "serve", "demo"))
    p.add_argument("--commit-dir", default=None,
                   help="shared dir: trainer commits+publishes, server "
                        "store-watches (required for train/serve)")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--commit-every", type=int, default=5)
    p.add_argument("--publish-every", type=int, default=1,
                   help="publish every Nth clean commit")
    p.add_argument("--step-s", type=float, default=0.0,
                   help="pause after each commit (demo pacing)")
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--poll-s", type=float, default=0.5)
    args = p.parse_args()
    if args.mode in ("train", "serve") and not args.commit_dir:
        raise SystemExit("--commit-dir is required for train/serve")
    {"train": train, "serve": serve, "demo": demo}[args.mode](args)


if __name__ == "__main__":
    main()
