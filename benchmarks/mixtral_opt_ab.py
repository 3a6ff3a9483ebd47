"""Interleaved A/B of expert-bank optimizer variants at the Mixtral bench
config (real TPU) — VERDICT r4 #2.

The r4 profile pinned the remaining Mixtral bound: the AdamW pass over
the 8x-overprovisioned expert bank is 12.8% of the step (~7.3 ms of pure
HBM traffic updating 176M mostly-inactive f32 params + 2x f32 moments).
Arms (optimizer/moe_opt.py; dense params keep exact AdamW in all of
them):

- ``adamw``     baseline (the r4 bench optimizer)
- ``bf16_nu``   expert v stored bf16 + stochastic rounding  (-1x v traffic)
- ``bf16_munu`` expert m AND v stored bf16                  (-2x m/v traffic)
- ``factored``  Adafactor for expert tensors (factored v, no m)
- ``deferred``  expert update every 4th step at 4x LR (skip = zero
                param/m/v traffic on 3 of 4 steps)

Interleaved (``slope_time_paired``) so run-to-run drift lands on every
arm; per-round ratios vs the ``adamw`` arm are the evidence.

Usage (real chip):  python benchmarks/mixtral_opt_ab.py [per_chip_batch]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
import numpy as np

from common import emit, lm_train_flops_per_token, mfu_fields, on_tpu, \
    params_count, slope_time_paired, sync

#: "deferred2" = TWO-program deferral (optimizer.deferred_pair): the
#: lax.cond "deferred" form measured ~flat because cond cannot alias the
#: pass-through moments; two jitted programs with donation CAN (the skip
#: program's expert bank aliases straight through). "deferred2_bf16nu"
#: stacks the bf16 second moment on the apply program.
VARIANTS = ("adamw", "bf16_nu", "bf16_munu", "factored", "deferred",
            "deferred2", "deferred2_bf16nu")


def main():
    import horovod_tpu as hvd
    from horovod_tpu.models.llama import LOGICAL_RULES
    from horovod_tpu.models.mixtral import (Mixtral, MixtralConfig,
                                            mixtral_tiny)
    from horovod_tpu.optimizer import moe_adamw
    from horovod_tpu.parallel import create_mesh
    from horovod_tpu.train import (create_gspmd_train_state,
                                   make_gspmd_train_step)

    hvd.init()
    n = hvd.size()
    tpu = on_tpu()
    if tpu:
        # shared bench config (scan_layers=False since r5) so variants
        # A/B at the adopted config (the r5 A/B table in
        # docs/benchmarks.md was measured on the scan config)
        from common import mixtral_bench_config
        cfg = mixtral_bench_config()
        pos = [a for a in sys.argv[1:] if not a.startswith("-")]
        per_chip, seq = (int(pos[0]) if pos else 16), 512
    else:
        cfg = mixtral_tiny()
        per_chip, seq = 2, 32
    batch = max(per_chip * n, 2)
    ep = min(cfg.n_experts, n)
    mesh = create_mesh({"dp": n // ep, "ep": ep}) if n > 1 \
        else create_mesh({"dp": 1})
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    model = Mixtral(cfg)

    def build(variant):
        if variant.startswith("deferred2"):
            from horovod_tpu.optimizer import deferred_pair
            from horovod_tpu.train import make_gspmd_deferred_train_step
            nu = jnp.bfloat16 if variant.endswith("bf16nu") else None
            pair = deferred_pair(1e-4, every=4, expert_nu_dtype=nu)
            state = create_gspmd_train_state(model, pair.apply,
                                             jax.random.PRNGKey(0), tokens,
                                             mesh, LOGICAL_RULES)
            step = make_gspmd_deferred_train_step(
                model, pair, mesh, LOGICAL_RULES,
                aux_weight=cfg.router_aux_weight, donate=True)
        else:
            opt = moe_adamw(1e-4, expert_variant=variant, every=4)
            state = create_gspmd_train_state(model, opt,
                                             jax.random.PRNGKey(0), tokens,
                                             mesh, LOGICAL_RULES)
            # donate=True (the bench setting): without donation the
            # lax.cond deferred variant COPIES the whole expert m/v
            # through on every skip step — the copy costs more than the
            # AdamW pass it skips (measured -14.6% with donate=False).
            step = make_gspmd_train_step(model, opt, mesh, LOGICAL_RULES,
                                         aux_weight=cfg.router_aux_weight,
                                         donate=True)
        box = {"state": state}

        def run(k):
            st, loss = box["state"], None
            for _ in range(k):
                st, loss = step(st, tokens)
            box["state"] = st
            sync(loss)

        return run, box

    # PAIRWISE vs the baseline (five full param+moment states at once OOM
    # a single chip): each pair interleaves {adamw, variant} so drift
    # lands on both arms; ratios are still per-round medians.
    # k=4/8 so the deferred arm's apply-step lands once per k=4 window
    # (its slope then reflects the AVERAGE step, apply + 3 skips).
    flops_tok = None
    for variant in VARIANTS[1:]:
        run_base, box_base = build("adamw")
        run_var, box_var = build(variant)
        if flops_tok is None:
            total = params_count(box_base["state"].params)
            expert = params_count(
                box_base["state"].params,
                select=lambda p: "moe" in p and p.rsplit("/", 1)[-1] in
                ("w1", "w2", "w3"))
            active = total - expert + expert * cfg.top_k / cfg.n_experts
            flops_tok = lm_train_flops_per_token(active, cfg.n_layers,
                                                 cfg.dim, seq)
        secs, rounds = slope_time_paired(
            {"adamw": run_base, variant: run_var}, 4, 8, return_rounds=True)
        ratio = float(np.median([r["adamw"] / r[variant] for r in rounds]))
        base_tps = batch * seq / secs["adamw"] / n
        tps = batch * seq / secs[variant] / n
        emit(f"mixtral_opt_{variant}_tokens_per_sec_per_chip", tps,
             f"tokens/sec/chip (seq {seq}, batch {per_chip}/chip, "
             f"expert_variant={variant}, {n} devices; paired adamw arm "
             f"{base_tps / 1000:.1f}k)",
             speedup_vs_adamw=round(ratio, 4),
             **mfu_fields(tps, flops_tok))
        del run_base, run_var, box_base, box_var
        import gc
        gc.collect()


if __name__ == "__main__":
    main()
