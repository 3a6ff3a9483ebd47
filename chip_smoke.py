"""First light: drive horovod_tpu's main paths once on the TPU and check them.

    python chip_smoke.py

Runs, through the public entry points a user calls and in ONE process over
however many chips are visible:

- train-resnet  ResNet-50 (bf16, space-to-depth stem, 224x224, batch 128 a
                chip, SGD+momentum) fed by ``horovod_tpu.data``;
- train-bert    BERT-Large (seq 512, batch 8 a chip, AdamW, bf16-compressed
                allreduce); the compiled step must hold a Mosaic kernel;
- kernels       ``flash_attention`` and ``fused_combine`` against their jnp
                oracles;
- adasum        the Adasum optimizer step and butterfly (on more than one
                chip this is where ``fused_combine`` runs inside shard_map);
- serve         a ``DecodeEngine`` behind ``InferenceServer`` answering two
                waves of ``POST /generate`` at Llama-3-8B WIDTHS cut to two
                layers, plus prefill/decode logits against ``Llama.apply``.
                The repo has no other public-width LM: this is a bring-up
                size, not a benchmark size.

It has no CPU mode, no flag and no environment switch that shrinks it: when
jax finds no TPU it says what it found and exits non-zero. Each phase is a
plain function of a config dict so tests/test_chip_smoke.py can rehearse it
at tiny sizes on the CPU mesh; ``main()`` only ever runs ``FULL``.

Every phase prints one JSON line of bring-up facts (not benchmark metrics):
first-call seconds (compile included) apart from steady milliseconds taken
by host clock around ``jax.block_until_ready`` after warm-up, and each
device's ``peak_bytes_in_use`` (a high-water mark since process start). A
phase that raises fails the run. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import concurrent.futures
import dataclasses
import json
import math
import os
import statistics
import time
import urllib.request


# ----------------------------------------------------------------- plumbing

def require_chip(devices):
    """The smoke path's only door: a TPU whose kind has a peak on record.
    Returns the device facts every phase line carries."""
    from horovod_tpu.tools.perf import _peak_for_kind
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; jax found platform {d.platform!r} "
            f"({d.device_kind!r} x {len(devices)})")
    if not math.isfinite(_peak_for_kind(d.device_kind)):
        raise SystemExit(
            f"chip_smoke: device kind {d.device_kind!r} has no entry in "
            "horovod_tpu/tools/perf.py _PEAK_TABLE")
    return {"platform": d.platform, "device_kind": d.device_kind,
            "n_devices": len(devices)}


def emit(**line):
    print(json.dumps(line), flush=True)


def peak_bytes():
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def run_phase(name, fn, cfg, dev):
    t0 = time.perf_counter()
    try:
        facts = fn(cfg)
    except BaseException as e:
        emit(phase=name, ok=False, **dev,
             error=f"{type(e).__name__}: {e}"[:2000])
        raise
    emit(phase=name, ok=True, **dev, **facts,
         phase_wall_s=round(time.perf_counter() - t0, 2),
         peak_bytes_in_use=peak_bytes())


def _ms(seconds):
    return round(seconds * 1e3, 3)


#: how a Pallas kernel that went through Mosaic reads in optimized HLO
_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def _cache_entries():
    import jax
    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


# ------------------------------------------------------- the training phases

def _feed(arrays, global_batch, steps):
    """``steps`` batches through the library's own input path: Dataset
    (native gather) -> Prefetcher thread -> shard_batch over the mesh."""
    from horovod_tpu.data import Dataset, Prefetcher
    ds = Dataset(arrays, batch_size=global_batch, shuffle=False)

    def epochs():
        for e in range(steps):
            ds.set_epoch(e)
            yield from ds
    return Prefetcher(epochs())


def _train(step, state, arrays, global_batch, steps):
    """Per-step dispatch with the step's defaults (donated state). Returns
    (state, losses, facts, last_batch)."""
    import jax
    losses, walls, waits = [], [], []
    with _feed(arrays, global_batch, steps) as feed:
        for _ in range(steps):
            t0 = time.perf_counter()
            batch = next(feed)
            t1 = time.perf_counter()
            state, loss = step(state, *batch)
            jax.block_until_ready(loss)
            t2 = time.perf_counter()
            waits.append(t1 - t0)
            walls.append(t2 - t1)
            losses.append(float(loss))
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    # The second call is set apart: on several devices the state goes in
    # uncommitted on device 0 and comes back replicated over the mesh, so
    # the jitted step is traced (and maybe compiled) once more.
    steady = walls[2:]
    facts = {
        "steps": steps,
        "first_call_s": round(walls[0], 2),         # trace + compile + 1 step
        "second_call_s": round(walls[1], 3),
        "steady_step_ms": _ms(statistics.median(steady)),
        "steady_step_ms_min_max": [_ms(min(steady)), _ms(max(steady))],
        "data_wait_ms": _ms(statistics.median(waits[2:])),
        "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
    }
    return state, losses, facts, batch


def _inspect(step, state, batch, n):
    """Facts read off the COMPILED step (``analysis/hlo.py``) and the state
    it returned: Mosaic kernels, the all-reduce's replica groups, and
    whether every param leaf really lives on every device."""
    import jax
    from horovod_tpu.analysis import hlo
    t0 = time.perf_counter()
    text = step.lower(state, *batch).compile().as_text()
    summary = hlo.summarize_optimized(text)
    groups = sorted({c.group_size for c in summary.collectives
                     if c.op == "all_reduce"})
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(state.params)}
    if spans != {n}:
        raise AssertionError(
            f"param leaves span {sorted(spans)} devices, expected {n}")
    if n > 1 and n not in groups:
        raise AssertionError(
            f"no {n}-member all-reduce in the compiled step (group sizes "
            f"{groups})")
    return {"inspect_compile_s": round(time.perf_counter() - t0, 2),
            "tpu_custom_calls": text.count(_MOSAIC_CALL),
            "allreduce_group_sizes": groups,
            "collective_permutes": summary.count("collective_permute"),
            "donated": summary.donated,
            "param_device_span": n}


def _require_mosaic(facts, at_least, what):
    if facts["tpu_custom_calls"] < at_least:
        raise AssertionError(
            f"{what}: compiled step holds {facts['tpu_custom_calls']} "
            f"tpu_custom_call(s), expected >= {at_least} — the Pallas "
            "kernel did not go through Mosaic")


def _slope_step_ms(model, dopt, loss_fn, state, batch, ks, rounds):
    """Slope timing, beside the per-step block_until_ready number: seconds
    per step as the slope between a short and a long device-side
    ``lax.scan`` of the step. A bring-up fact, not a benchmark metric
    (ROADMAP D9: these timing lines are the remainder to remove)."""
    import jax
    import numpy as np
    from horovod_tpu.train import make_train_step
    steps = {k: make_train_step(model, dopt, loss_fn, scan_steps=k,
                                donate=False) for k in ks}
    best = {}
    t0 = time.perf_counter()
    for k in ks:                                  # compile + warm
        jax.block_until_ready(steps[k](state, *batch)[1])
    compile_s = time.perf_counter() - t0
    for _ in range(rounds):
        for k in ks:
            t = time.perf_counter()
            np.asarray(steps[k](state, *batch)[1])      # device->host sync
            best[k] = min(best.get(k, math.inf), time.perf_counter() - t)
    short, long_ = ks
    return {"slope_step_ms": _ms((best[long_] - best[short])
                                 / (long_ - short)),
            "slope_scan_lengths": list(ks),
            "slope_compile_s": round(compile_s, 2)}


def phase_train_resnet(cfg):
    import jax
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    n = hvd.size()
    batch = cfg["per_chip_batch"] * n
    rng = np.random.RandomState(cfg["seed"])
    images = rng.randn(batch, cfg["image"], cfg["image"], 3).astype(
        np.float32)
    labels = rng.randint(0, cfg["classes"], size=(batch,)).astype(np.int32)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    model = cfg["model"](hvd.RANK_AXIS)
    dopt = distributed(optax.sgd(0.1, momentum=0.9))
    t0 = time.perf_counter()
    state = create_train_state(model, jax.random.PRNGKey(cfg["seed"]),
                               images[:1], dopt)
    init_s = time.perf_counter() - t0
    step = make_train_step(model, dopt, loss_fn)
    state, losses, facts, last = _train(step, state, (images, labels), batch,
                                        cfg["steps"])
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    facts.update(_inspect(step, state, last, n))
    facts.update(_slope_step_ms(model, dopt, loss_fn, state, last,
                                cfg["slope_scan_lengths"],
                                cfg["slope_rounds"]))
    return {"global_batch": batch, "init_s": round(init_s, 2), **facts}


def phase_train_bert(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.collectives import Compression
    from horovod_tpu.models.bert import Bert
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    n = hvd.size()
    mcfg, seq = cfg["model_cfg"], cfg["seq"]
    batch = cfg["per_chip_batch"] * n
    rng = np.random.RandomState(cfg["seed"])
    tokens = rng.randint(0, mcfg.vocab_size, (batch, seq)).astype(np.int32)
    raw = rng.randint(0, mcfg.vocab_size, (batch, seq))
    # labels carry their own mask (-1 = not an MLM position) so they shard
    # with the batch like any other per-example tensor
    labels = np.where(rng.rand(batch, seq) < 0.15, raw, -1).astype(np.int32)

    def loss_fn(logits, y):
        valid = y >= 0
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.maximum(y, 0))
        return (ce * valid).sum() / jnp.maximum(valid.sum(), 1)

    model = Bert(mcfg)
    dopt = distributed(optax.adamw(1e-4), compression=Compression.bf16)
    t0 = time.perf_counter()
    state = create_train_state(model, jax.random.PRNGKey(cfg["seed"]),
                               tokens[:1], dopt)
    init_s = time.perf_counter() - t0
    step = make_train_step(model, dopt, loss_fn)
    state, _, facts, last = _train(step, state, (tokens, labels), batch,
                                   cfg["steps"])
    facts.update(_inspect(step, state, last, n))
    _require_mosaic(facts, cfg["min_tpu_custom_calls"], "train-bert")
    return {"global_batch": batch, "seq": seq, "n_layers": mcfg.n_layers,
            "init_s": round(init_s, 2), **facts}


# --------------------------------------------------------------- the kernels

def _close(got, ref, tol, what):
    """Every tolerance in this file means: max|got - ref| <= tol * max(1,
    max|ref|), compared in float32 on the host. Returns the error."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(got - ref)))
    bound = tol * max(1.0, float(np.max(np.abs(ref))))
    if not (np.isfinite(got).all() and err <= bound):
        raise AssertionError(f"{what}: max abs err {err:.3e} > {bound:.3e}")
    return err


def _flash_case(case):
    """One shape: forward and jax.grad of ``flash_attention`` against
    ``_reference_partial`` under the same random cotangent."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.ops import flash_attention, merge_partials
    from horovod_tpu.ops.flash_attention import _reference_partial

    B, T, H, D = case["shape"]
    dtype = jnp.dtype(case["dtype"])
    rng = np.random.RandomState(case["seed"])
    q, k, v, w = (jnp.asarray(rng.randn(B, T, H, D), dtype)
                  for _ in range(4))
    scale = D ** -0.5
    causal = case["causal"]
    mask = bias = None
    if case["masked"]:                      # BERT-style key padding mask
        lens = rng.randint(T // 2, T + 1, size=(B,))
        mask = jnp.asarray(np.arange(T)[None, :] < lens[:, None])
        bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)

    def f_kernel(q, k, v):
        o = flash_attention(q, k, v, causal=causal, kv_mask=mask)
        return (o.astype(jnp.float32) * w.astype(jnp.float32)).sum(), o

    def f_ref(q, k, v):
        o, _, _ = _reference_partial(q, k, v, bias, causal=causal,
                                     scale=scale)
        return (o.astype(jnp.float32) * w.astype(jnp.float32)).sum(), o

    t0 = time.perf_counter()
    g_kernel = jax.jit(jax.grad(f_kernel, argnums=(0, 1, 2), has_aux=True))
    (dq, dk, dv), o = jax.block_until_ready(g_kernel(q, k, v))
    first_call_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(g_kernel(q, k, v))
    steady = time.perf_counter() - t0
    (rq, rk, rv), ro = jax.jit(
        jax.grad(f_ref, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    errs = {"fwd": _close(o, ro, case["tol_fwd"], f"flash fwd {case}")}
    for name, got, ref in (("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
        errs[name] = _close(got, ref, case["tol_grad"],
                            f"flash {name} {case}")
    out = {"shape": [B, T, H, D], "causal": causal, "masked": case["masked"],
           "dtype": case["dtype"], "first_call_s": round(first_call_s, 2),
           "fwd_bwd_ms": _ms(steady),
           "tol": [case["tol_fwd"], case["tol_grad"]],
           "max_abs_err": {k_: float(f"{e:.3e}") for k_, e in errs.items()}}
    if case["residuals"]:
        # two key halves, each a partial with (m, l); merged they must be
        # the full attention — the property ring attention rests on
        half = T // 2

        @jax.jit
        def merged(q, k, v):
            parts = []
            for sl in (slice(0, half), slice(half, T)):
                o_, (m_, l_) = flash_attention(
                    q, k[:, sl], v[:, sl], causal=False,
                    return_residuals=True)
                parts.append((o_, m_, l_))
            return merge_partials(*parts)

        mo, mm, ml = merged(q, k, v)
        fo, fm, fl = _reference_partial(q, k, v, causal=False, scale=scale)
        out["max_abs_err"]["merged_o"] = float(
            f"{_close(mo, fo, case['tol_fwd'], 'merged o'):.3e}")
        _close(mm, fm, 1e-3, "merged m")
        _close(ml / fl, jnp.ones_like(fl), case["tol_fwd"], "merged l")
    return out


def _combine_case(size, tol):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.collectives.adasum import _combine
    from horovod_tpu.ops import fused_combine

    rng = np.random.RandomState(size % 9973)
    a = rng.randn(size).astype(np.float32)
    b = (0.5 * a + rng.randn(size)).astype(np.float32)   # a.b far from 0
    a, b = jnp.asarray(a), jnp.asarray(b)
    t0 = time.perf_counter()
    got = jax.block_until_ready(fused_combine(a, b))
    first_call_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fused_combine(a, b))
    steady = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):   # an exact f32 oracle
        ref = jax.jit(_combine)(a, b)
    err = _close(got, ref, tol, f"fused_combine n={size}")
    return {"elements": size, "first_call_s": round(first_call_s, 2),
            "ms": _ms(steady), "tol": tol,
            "max_abs_err": float(f"{err:.3e}")}


def phase_kernels(cfg):
    return {"flash_attention": [_flash_case(c) for c in cfg["flash"]],
            "fused_combine": [_combine_case(s, cfg["combine_tol"])
                              for s in cfg["combine_sizes"]]}


# -------------------------------------------------------------------- adasum

def _adasum_butterfly_f64(rows):
    """The XOR butterfly of collectives/adasum.py in numpy float64."""
    import numpy as np
    rows = [np.asarray(r, np.float64) for r in rows]
    n, d = len(rows), 1
    while d < n:
        nxt = []
        for r in range(n):
            a, b = rows[r], rows[r ^ d]
            dot, na, nb = a @ b, a @ a, b @ b
            ca = 1.0 - dot / (2.0 * na) if na > 0 else 1.0
            cb = 1.0 - dot / (2.0 * nb) if nb > 0 else 1.0
            nxt.append(ca * a + cb * b)
        rows, d = nxt, d * 2
    return np.stack(rows)


def phase_adasum(cfg):
    """examples/train_adasum.py's optimizer. On one chip the butterfly has
    zero rounds; on several, each round's combine is ``fused_combine``
    inside the DP shard_map (TPU, >= 65,536-element f32 working vector)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    import horovod_tpu as hvd
    from horovod_tpu.models.llama import Llama
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import (create_train_state, make_train_step,
                                   next_token_loss)

    n = hvd.size()
    # (a) the collective alone, against float64
    rng = np.random.RandomState(cfg["seed"])
    x = rng.randn(n, cfg["vector"]).astype(np.float32)
    fn = jax.jit(shard_map(
        lambda v: hvd.adasum_allreduce(v), mesh=hvd.mesh(),
        in_specs=P(hvd.RANK_AXIS), out_specs=P(hvd.RANK_AXIS),
        check_vma=False))
    got = fn(jnp.asarray(x))
    err = _close(got, _adasum_butterfly_f64(x), cfg["tol"],
                 "adasum butterfly")
    # (b) the optimizer step
    mcfg = cfg["model_cfg"]
    batch = cfg["per_chip_batch"] * n
    tokens = rng.randint(1, mcfg.vocab_size, (batch, cfg["seq"])).astype(
        np.int32)
    model = Llama(mcfg)
    dopt = distributed(optax.adamw(1e-3), op=hvd.Adasum)
    state = create_train_state(model, jax.random.PRNGKey(cfg["seed"]),
                               tokens[:1], dopt)
    step = make_train_step(model, dopt, next_token_loss)
    state, _, facts, last = _train(step, state, (tokens, tokens), batch,
                                   cfg["steps"])
    facts.update(_inspect(step, state, last, n))
    if n > 1:
        _require_mosaic(facts, cfg["min_tpu_custom_calls_multi"], "adasum")
        if facts["collective_permutes"] < 1:
            raise AssertionError("adasum step has no collective-permute")
    grad_elems = sum(int(np.prod(leaf.shape))
                     for leaf in jax.tree_util.tree_leaves(state.params))
    return {"butterfly_rounds": int(math.log2(n)),
            "butterfly_elements": cfg["vector"], "tol": cfg["tol"],
            "butterfly_max_abs_err": float(f"{err:.3e}"),
            "working_vector_elements": grad_elems, **facts}


# --------------------------------------------------------------------- serve

def _post_generate(addr, prompt, max_new, timeout_s):
    req = urllib.request.Request(
        f"http://{addr}/generate",
        data=json.dumps({"tokens": prompt, "max_new": max_new}).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout_s) as r:
        body = json.loads(r.read())
    return body, time.perf_counter() - t0


def _wave(addr, prompts, max_new, timeout_s):
    """All prompts at once; every request must come back whole."""
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futs = [pool.submit(_post_generate, addr, p, max_new, timeout_s)
                for p in prompts]
        results = [f.result() for f in futs]
    wall = time.perf_counter() - t0
    for prompt, (body, _) in zip(prompts, results):
        new = body["tokens"][len(prompt):]
        if not (body["ok"] and not body["truncated"]
                and body["tokens"][:len(prompt)] == prompt
                and len(new) == max_new):
            raise AssertionError(
                f"prompt of {len(prompt)}: got {len(new)} new tokens, "
                f"wanted {max_new}: {str(body)[:300]}")
    return wall, [lat for _, lat in results]


def _logit_check(model, mcfg, params, cfg, rng):
    """Correctness by logits (greedy streams flip on rounding: ROADMAP D0):
    ``make_prefill`` at the last prompt position, then one
    ``make_decode_step`` after it, each against ``Llama.apply`` on the same
    prefix."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models import decode as MD
    from horovod_tpu.serving.server import pad_to_bucket

    bs, plen = cfg["block_size"], cfg["check_prompt_len"]
    bucket = pad_to_bucket(plen, cfg["prefill_buckets"])
    prompt = rng.randint(1, mcfg.vocab_size, (plen,)).astype(np.int32)
    apply = jax.jit(lambda p, t: model.apply({"params": p}, t, train=False))
    blocks = np.arange(1, bucket // bs + 1, dtype=np.int32)
    n_blocks = len(blocks) + 2              # + null block 0 + one to grow into
    kp, vp = MD.init_kv_pools(mcfg, n_blocks, bs)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :plen] = prompt
    logits, kp, vp = jax.jit(MD.make_prefill(mcfg, bs))(
        params, kp, vp, jnp.asarray(padded), jnp.asarray(blocks))
    ref = apply(params, jnp.asarray(prompt[None]))
    err_prefill = _close(logits[0, plen - 1], ref[0, plen - 1],
                         cfg["logit_tol"], "prefill logits")
    nxt = int(jnp.argmax(ref[0, plen - 1]))
    table = np.zeros((1, n_blocks - 1), np.int32)
    table[0, :len(blocks)] = blocks
    if plen // bs >= len(blocks):           # the new position's block
        table[0, plen // bs] = len(blocks) + 1
    dlogits, _, kp, vp = jax.jit(MD.make_decode_step(mcfg, bs))(
        params, kp, vp, jnp.asarray([nxt], jnp.int32),
        jnp.asarray([plen], jnp.int32), jnp.asarray(table),
        jnp.asarray([True]))
    ref2 = apply(params, jnp.asarray(np.append(prompt, nxt)[None]))
    err_decode = _close(dlogits[0], ref2[0, plen], cfg["logit_tol"],
                        "decode-step logits")
    return {"logit_tol": cfg["logit_tol"],
            "logit_abs_max": round(float(jnp.max(jnp.abs(ref[0, plen - 1]))),
                                   3),
            "prefill_logit_max_abs_err": float(f"{err_prefill:.3e}"),
            "decode_logit_max_abs_err": float(f"{err_decode:.3e}")}


def phase_serve(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models.llama import Llama
    from horovod_tpu.serving import InferenceServer, ModelRegistry
    from horovod_tpu.serving.decode import DecodeEngine
    from horovod_tpu.train import export_decode_params

    mcfg, bs = cfg["model_cfg"], cfg["block_size"]
    buckets, max_new = cfg["prefill_buckets"], cfg["max_new"]
    rng = np.random.RandomState(cfg["seed"])
    model = Llama(mcfg)
    t0 = time.perf_counter()
    variables = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32), train=False))(
            jax.random.PRNGKey(cfg["seed"]))
    params = export_decode_params(variables)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    logit_facts = _logit_check(model, mcfg, params, cfg, rng)

    def prompts():
        # half the wave in each prefill bucket
        out, lo = [], 1
        for hi in buckets:
            for _ in range(cfg["wave"] // len(buckets)):
                ln = int(rng.randint(max(lo, hi // 2), hi + 1))
                out.append([int(t) for t in rng.randint(
                    1, mcfg.vocab_size, (ln,))])
            lo = hi + 1
        return out

    # -- the server answers requests
    ctx_blocks = -(-(buckets[-1] + max_new) // bs)
    engine = DecodeEngine(
        mcfg, params=params, slots=cfg["slots"], block_size=bs,
        pool_blocks=cfg["slots"] * ctx_blocks + 1,
        max_blocks_per_slot=ctx_blocks, prefill_buckets=buckets, spec_k=0)
    srv = InferenceServer(ModelRegistry(), lambda payload, inputs, n: [],
                          request_timeout_s=cfg["request_timeout_s"],
                          decode_engine=engine)
    try:
        wall1, _ = _wave(srv.addr(), prompts(), max_new,
                         cfg["request_timeout_s"])
        counts1 = dict(engine.compile_counts)
        wall2, lat2 = _wave(srv.addr(), prompts(), max_new,
                            cfg["request_timeout_s"])
        counts2 = dict(engine.compile_counts)
    finally:
        srv.close()
    if counts2 != counts1 or counts1.get("prefill") != len(buckets) \
            or counts1.get("decode") != 1:
        raise AssertionError(
            f"compile counts moved or are not one-per-program: wave 1 "
            f"{counts1}, wave 2 {counts2}")
    return {"model": cfg["model_note"], "params": n_params, "init_s": round(init_s, 2), **logit_facts,
            "wave_requests": cfg["wave"], "max_new": max_new,
            "prefill_buckets": list(buckets),
            "first_wave_s": round(wall1, 2),     # compiles included
            "steady_wave_ms": _ms(wall2),
            "steady_request_ms": _ms(statistics.median(lat2)),
            "steady_tokens_per_wave": cfg["wave"] * max_new,
            "compile_counts": counts2}


# ------------------------------------------------------------------- configs

def full_configs():
    """The one size main() runs: full width, real batch, depth cut only
    where stated."""
    import jax.numpy as jnp
    from horovod_tpu.models import ResNet50
    from horovod_tpu.models.bert import bert_large
    from horovod_tpu.models.llama import LlamaConfig, llama3_8b

    bf16 = {"dtype": "bfloat16", "tol_fwd": 2e-2, "tol_grad": 5e-2}
    return {
        "train-resnet": {
            "model": lambda axis: ResNet50(axis_name=axis,
                                           dtype=jnp.bfloat16,
                                           stem="space_to_depth"),
            "image": 224, "classes": 1000, "per_chip_batch": 128,
            "steps": 10, "seed": 0,
            "slope_scan_lengths": (4, 24), "slope_rounds": 3},
        "train-bert": {
            "model_cfg": bert_large(), "seq": 512, "per_chip_batch": 8,
            "steps": 4, "seed": 1, "min_tpu_custom_calls": 1},
        "kernels": {
            "flash": [
                {"shape": (2, 4096, 8, 128), "causal": True, "masked": False,
                 "residuals": False, "seed": 2, **bf16},
                {"shape": (8, 512, 16, 64), "causal": False, "masked": True,
                 "residuals": False, "seed": 3, **bf16},
                {"shape": (2, 2048, 8, 128), "causal": False,
                 "masked": False, "residuals": True, "seed": 4, **bf16},
            ],
            "combine_sizes": [1 << 20, (1 << 20) + 12345],
            "combine_tol": 1e-4},
        "adasum": {
            "vector": 1 << 17, "tol": 1e-4, "seed": 5,
            "model_cfg": LlamaConfig(
                vocab_size=512, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
                hidden_dim=512, max_seq_len=128, dtype=jnp.float32,
                remat=False, scan_layers=False),
            "seq": 128, "per_chip_batch": 2, "steps": 3,
            "min_tpu_custom_calls_multi": 1},
        "serve": {
            "model_note": "llama3-8b widths, 2 of 32 layers, random weights "
                          "— bring-up size, not a benchmark size",
            "model_cfg": dataclasses.replace(
                llama3_8b(), n_layers=2, max_seq_len=512, remat=False,
                scan_layers=False),
            "block_size": 16, "prefill_buckets": (64, 256), "slots": 8,
            "wave": 8, "max_new": 16, "check_prompt_len": 200,
            "logit_tol": 5e-2, "request_timeout_s": 900.0, "seed": 6},
    }


PHASES = (("train-resnet", phase_train_resnet),
          ("train-bert", phase_train_bert),
          ("kernels", phase_kernels),
          ("adasum", phase_adasum),
          ("serve", phase_serve))


def main():
    import jax
    import horovod_tpu as hvd   # places the compile cache; takes no device
    # First touch of the backend. Under the launcher this joins the
    # coordination service first, which jax requires before jax.devices().
    hvd.init()
    devices = jax.devices()
    dev = require_chip(devices)
    from horovod_tpu import native
    from horovod_tpu.native.build import lib_path
    entries0 = _cache_entries()
    emit(phase="start", ok=True, **dev,
         jax=jax.__version__, processes=jax.process_count(),
         native_available=native.available(),
         native_lib=os.path.relpath(lib_path()) if native.available()
         else "no C++ compiler on this machine",
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         compile_cache_dir_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         compile_cache_entries=entries0)
    configs = full_configs()
    t0 = time.perf_counter()
    for name, fn in PHASES:
        run_phase(name, fn, configs[name], dev)
    entries1 = _cache_entries()
    emit(phase="end", ok=True, **dev,
         total_wall_s=round(time.perf_counter() - t0, 2),
         compile_cache_entries=entries1,
         compile_cache_entries_added=entries1 - entries0)
    hvd.shutdown()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
