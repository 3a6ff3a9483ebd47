"""Interleaved A/B of Llama remat policies at the bench config (real TPU).

r4 follow-up to the Llama op profile: 33.7% of the step is elementwise +
full-remat recompute and the flash kernels (32.6%) run their forward
TWICE per step under ``remat_policy="full"``. The "attn" policy saves
the flash kernel's (o, m, l) by name (ops/flash_attention.py) so the
backward runs only the dedicated bwd kernels. POLICIES below picks the
arms — default full vs attn, the two that FIT at the bench batch (the
"dots" family saves non-batch dot outputs, ~7 GB at this shape, and
OOMs at batch 8; it was measured at batch 4 and for the longctx/Mixtral
shapes instead). Interleaved (``slope_time_paired``) so run-to-run drift
lands on both arms.

Usage (real chip):  python benchmarks/llama_remat_ab.py [per_chip_batch]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from common import emit, lm_train_flops_per_token, mfu_fields, on_tpu, \
    params_count, slope_time_paired, sync

POLICIES = ("full", "attn")


def main():
    import horovod_tpu as hvd
    from horovod_tpu.models.llama import Llama, LlamaConfig, llama_tiny
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import (create_train_state, make_train_step,
                                   next_token_loss)
    import dataclasses

    hvd.init()
    n = hvd.size()
    tpu = on_tpu()
    if tpu:
        base = LlamaConfig(vocab_size=32000, dim=1024, n_layers=24,
                           n_heads=16, n_kv_heads=8, hidden_dim=4096,
                           max_seq_len=2048)
        pos = [a for a in sys.argv[1:] if not a.startswith("-")]
        per_chip, seq = (int(pos[0]) if pos else 8), 1024
    else:
        base = dataclasses.replace(llama_tiny(), remat=True,
                                   use_flash=True, scan_layers=True)
        per_chip, seq = 2, 32
    batch = per_chip * n
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, base.vocab_size, (batch, seq)))

    dopt = distributed(optax.adamw(1e-4))
    # ONE state shared across policies (the remat policy does not change
    # the param/opt pytree); donate=False keeps it reusable.
    model0 = Llama(dataclasses.replace(base, remat_policy="full"))
    state = create_train_state(model0, jax.random.PRNGKey(0), tokens[:1],
                               dopt)

    def loss_fn(logits, y):
        return next_token_loss(logits, y)

    runs = {}
    for pol in POLICIES:
        model = Llama(dataclasses.replace(base, remat_policy=pol))
        steps = {k: make_train_step(model, dopt, loss_fn, scan_steps=k,
                                    donate=False) for k in (2, 8)}

        def run(k, _steps=steps):
            _, loss = _steps[k](state, tokens, tokens)
            sync(loss)

        runs[pol] = run

    secs, rounds = slope_time_paired(runs, 2, 8, return_rounds=True)
    flops_tok = lm_train_flops_per_token(
        params_count(state.params), base.n_layers, base.dim, seq)
    ratios = {p: float(np.median([r["full"] / r[p] for r in rounds]))
              for p in POLICIES}
    for pol in POLICIES:
        tps = batch * seq / secs[pol] / n
        emit(f"llama_remat_{pol}_tokens_per_sec_per_chip", tps,
             f"tokens/sec/chip (seq {seq}, batch {per_chip}/chip, "
             f"remat_policy={pol}, {n} devices)",
             speedup_vs_full=round(ratios[pol], 4),
             **mfu_fields(tps, flops_tok))


if __name__ == "__main__":
    main()
