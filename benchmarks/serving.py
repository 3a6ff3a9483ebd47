"""Serving-plane harness: hot-swap latency vs changed-leaf fraction,
request survival across swaps, and commit→served staleness.

ISSUE 10 acceptance: the CAS delta-fetch must make a mostly-frozen
update (one changed leaf, e.g. a fine-tuned head) STRICTLY cheaper to
adopt than an all-leaves update of the same model — that is the whole
point of content-addressed publishing. A/B in ONE run (CLAUDE.md:
interleaved rounds, ratios not absolutes — never separate blocks): each
round pair times

- **all** swaps — every leaf changes between generations (worst case:
  the registry must fetch + verify every blob), then
- **frozen** swaps — one leaf changes, the rest are byte-identical
  (best case: unchanged digests come from the registry's leaf cache,
  zero-copy).

Measured per round: median adopt() wall per swap, blobs fetched vs
leaves reused, and the per-round all/frozen ratio with its noise band.

Two correctness segments ride along:

- **traffic** — an InferenceServer answers a steady request stream
  while ≥2 hot-swaps land mid-traffic; EVERY request must return ok
  (zero dropped, zero failures) and the served model_seq must advance;
- **staleness** — a store-watch registry follows a timed commit+publish
  cadence; commit→served latency (adopted_at − publish time) must stay
  a small fraction of the cadence.

The **decode** segment (ISSUE 13) A/Bs continuous-batching decode
(serving/decode.py: paged KV-cache + persistent slot array) against the
bucketed full-forward serving arm it replaces, on llama_tiny over the
CPU mesh. Same interleaving discipline: both arms inside every
``slope_time_paired`` round, speedup = median of per-round ratios. Also
recorded: decode tokens/s/chip, TTFT at admission, steady-state decode
compile count (must be ZERO after warmup — the no-recompile contract),
and the p99 per-step latency while ≥2 weight hot-swaps land mid-decode
(the refill-policy block-table remap cost). A shed probe (ISSUE 19
satellite) rides in the decode record: a burst of concurrent requests
against a server pinned to a tiny admission queue
(``HOROVOD_SERVING_QUEUE_MAX=2``) while the worker is held busy, so the
shedding path has a measured baseline — ``shed_fraction`` must land
strictly inside (0, 1) (some requests shed with 429 + Retry-After, the
accepted ones all complete ok, none fail any other way).

The **sharded_decode** segment (ISSUE 14) scales the decode plane over
a ``tp`` mesh: tp=1 vs tp=4/8 arms at a FIXED per-device KV budget
(head-sharded pools ⇒ tp× slots at the same per-device bytes), both
LLMs, all arms interleaved inside every ``slope_time_paired`` round.
Because this box's 8 XLA devices timeshare one CPU core, raw wall-clock
cannot show tp speedup; the recorded scaling is device-time-NORMALIZED
tokens/s (``slots*devices/wall``, unit string in the record) with raw
walls alongside. Also recorded: per-arm steady-state compile counts
(must be 0) and the per-shard CAS swap-bytes probe — an all-leaves swap
adopted by a shard-selecting replica registry vs a whole-leaf registry,
railed at ``replica <= full/tp * 1.25``.

Sharded-decode noise band (satellite of ISSUE 16): the committed
mixtral tp8_vs_tp1 ratio is large (~9–14) because the normalization
credits tp× device concurrency, so its ABSOLUTE spread is large too;
the honest figure is the RELATIVE spread (spread / ratio_min). The
windows here (``rounds=4, s_short=3, s_long=9``) hold the relative
spread under ~0.45 on this box; tests/test_serving_decode_guardrail.py
pins that ceiling on the committed record.

The **spec_decode** segment (ISSUE 16) A/Bs speculative decode
(host n-gram drafting + one-shot k-token verify, serving/decode.py)
against the plain one-token engine, SAME model/slots/pool, two
workloads inside every interleaved round:

- **repeat_heavy** — a periodic prompt; greedy decode of the tiny
  model settles into a loop the built-in n-gram drafter locks onto,
  so accepted length approaches k−1 and tokens/s must reach
  ≥1.5× plain;
- **adversarial** — random-token prompts plus an injected
  always-wrong drafter (next = last+1 mod V): every draft is
  rejected, the verify still emits its one guaranteed token per tick,
  and tokens/s must hold ≥0.9× plain — the lossless-fallback rail.

Arms run at a LONG context provision (3072-position tables) — the
memory-bound regime speculative decode targets, where the k-wide
verify's wall equals a decode tick's (at short tables the per-token
weight math dominates and verify reads ~10% slower). Because spec
emits a VARIABLE number of tokens per tick, window-pair slope
differencing breaks (token and wall deltas fluctuate independently);
each figure is a synced token RATE over a ~25-tick window, engines
warmed past the repeat stream's ~25-token transient first. Zero
steady-state recompiles required in every arm; the per-arm ratios
land in perf_history as ``kind: "spec_decode"`` records ratcheted by
``tools.perf check``.

Emits one JSON line and appends it — stamped with
date + git SHA — to ``benchmarks/serving_history.jsonl`` unless
``HOROVOD_SERVING_NO_HISTORY`` is set. ``--check`` validates the newest
history record the way tests/test_control_plane_guardrail.py pins the
control-plane series; ``--smoke N`` runs a shrunk round for the chaos
tier.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time
import urllib.request
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np                                             # noqa: E402

from benchmarks import common  # noqa: E402,F401  (forces cpu backend)
from horovod_tpu.elastic.state import ObjectState              # noqa: E402
from horovod_tpu.serving import (InferenceServer, ModelRegistry,  # noqa: E402
                                 Publisher)
from horovod_tpu.serving import constants as SC                # noqa: E402

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "serving_history.jsonl")
NO_HISTORY_ENV = "HOROVOD_SERVING_NO_HISTORY"

#: --check rails. The frozen arm must be STRICTLY cheaper (acceptance);
#: the rail sits above 1.0 by less than any observed spread so only a
#: real delta-fetch regression can cross it.
MIN_SWAP_RATIO = 1.2
MAX_STALENESS_S = 2.0
#: Decode rails (ISSUE 13 acceptance): continuous decode must hold ≥2×
#: tokens/s over bucketed full-forward serving, with zero steady-state
#: decode compiles; the p99 ceiling is a loose absolute backstop — the
#: honest swap cost is the recorded p99/p50 pair itself.
MIN_DECODE_SPEEDUP = 2.0
MAX_DECODE_P99_S = 5.0
#: Sharded-decode rails (ISSUE 14 acceptance): normalized tokens/s at
#: tp=8 must scale >= 3x over tp=1 (fixed per-device KV budget, tp×
#: slots), with zero steady-state compiles in every arm; a replica
#: host's all-leaves swap bytes must stay within 1.25x of its 1/tp
#: share of the full-leaf bytes.
MIN_TP8_SCALING = 3.0
SHARD_SWAP_SLACK = 1.25
#: Spec-decode rails (ISSUE 16 acceptance): with a drafter that hits
#: (repeat-heavy stream) speculative decode must deliver ≥1.5× plain
#: tokens/s; with an always-wrong drafter (adversarial stream) it must
#: not fall below 0.9× plain — rejection costs one k-wide verify that
#: still emits its guaranteed token, never a stall.
MIN_SPEC_REPEAT_SPEEDUP = 1.5
MIN_SPEC_ADVERSARIAL_RATIO = 0.9


def _counters_clean() -> Dict[str, int]:
    # The bench trains nothing: the sentinel window is vacuously clean.
    return {"steps_skipped": 0, "rollbacks": 0}


# -- swap-latency arms --------------------------------------------------------


def _leaves(n_leaves: int, leaf_elems: int, gen: int, mode: str) -> dict:
    """Generation ``gen``'s attr dict. mode=all: every leaf differs per
    gen; mode=frozen: only leaf 0 does (the rest are byte-identical, so
    their blobs dedup to the same digests)."""
    out = {}
    for i in range(n_leaves):
        # gen*1000 + i keeps every (gen, leaf) pair's content unique —
        # a plain gen + i would alias leaf i at gen g with leaf i-1 at
        # gen g+1 and the digest cache would defeat the "all" arm.
        base = float(gen * 1000 if (mode == "all" or i == 0) else 0)
        out[f"w{i}"] = np.full(leaf_elems, base + i, dtype=np.float32)
    return out


def run_swap_round(mode: str, *, swaps: int, n_leaves: int,
                   leaf_elems: int) -> dict:
    """Fresh commit dir + publisher + registry; ``swaps`` timed
    generation adoptions under ``mode``; returns the round's metrics."""
    with tempfile.TemporaryDirectory(prefix="hvd_serving_bench_") as d:
        state = ObjectState(commit_dir=d, commit_async=False,
                            **_leaves(n_leaves, leaf_elems, 0, mode))
        pub = Publisher(d, every=1, counters=_counters_clean)
        reg = ModelRegistry(store=pub.store)
        state.commit()
        rec = pub.maybe_publish(state._commit_seq)
        assert rec is not None and reg.adopt(rec)   # warm adopt, untimed
        adopt_s: List[float] = []
        fetched0 = reg.stats["blobs_fetched"]
        reused0 = reg.stats["leaves_reused"]
        for gen in range(1, swaps + 1):
            for k, v in _leaves(n_leaves, leaf_elems, gen, mode).items():
                setattr(state, k, v)
            state.commit()
            rec = pub.maybe_publish(state._commit_seq)
            assert rec is not None, f"publish gate blocked gen {gen}"
            t0 = time.perf_counter()
            ok = reg.adopt(rec)
            adopt_s.append(time.perf_counter() - t0)
            assert ok, f"adopt rejected gen {gen}"
        return {
            "mode": mode, "swaps": swaps, "n_leaves": n_leaves,
            "leaf_kb": round(leaf_elems * 4 / 1024, 1),
            "adopt_s_median": round(statistics.median(adopt_s), 6),
            "blobs_fetched_per_swap": round(
                (reg.stats["blobs_fetched"] - fetched0) / swaps, 2),
            "leaves_reused_per_swap": round(
                (reg.stats["leaves_reused"] - reused0) / swaps, 2),
        }


# -- traffic across hot-swaps -------------------------------------------------


def run_traffic_segment(*, swaps: int, n_leaves: int, leaf_elems: int,
                        clients: int = 4,
                        requests_per_client: int = 25) -> dict:
    """A steady request stream with ``swaps`` hot-swaps landing
    mid-traffic; every request must come back ok."""
    with tempfile.TemporaryDirectory(prefix="hvd_serving_bench_") as d:
        state = ObjectState(commit_dir=d, commit_async=False,
                            **_leaves(n_leaves, leaf_elems, 0, "frozen"))
        pub = Publisher(d, every=1, counters=_counters_clean)
        reg = ModelRegistry(store=pub.store)
        state.commit()
        reg.adopt(pub.maybe_publish(state._commit_seq))

        def forward(payload, inputs, padded_n):
            w0 = payload["attrs"]["w0"]
            return [float(q["x"]) + float(w0[0]) for q in inputs]

        srv = InferenceServer(reg, forward, window_s=0.002,
                              request_timeout_s=30.0)
        results = {"sent": 0, "ok": 0, "failed": 0}
        lock = threading.Lock()
        seqs_served = set()

        def client_loop():
            for i in range(requests_per_client):
                body = json.dumps({"x": float(i)}).encode()
                req = urllib.request.Request(
                    f"http://{srv.addr()}/predict", data=body,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        out = json.loads(r.read())
                    good = bool(out.get("ok"))
                    seq = out.get("model_seq")
                except (OSError, ValueError):
                    good, seq = False, None
                with lock:
                    results["sent"] += 1
                    results["ok" if good else "failed"] += 1
                    if seq is not None:
                        seqs_served.add(seq)
                time.sleep(0.005)

        threads = [threading.Thread(target=client_loop, daemon=True)
                   for _ in range(clients)]
        try:
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            # Land the swaps while the stream is in flight.
            for gen in range(1, swaps + 1):
                time.sleep(0.15)
                for k, v in _leaves(n_leaves, leaf_elems, gen,
                                    "frozen").items():
                    setattr(state, k, v)
                state.commit()
                assert reg.adopt(pub.maybe_publish(state._commit_seq))
            for t in threads:
                t.join(timeout=120)
            elapsed = time.perf_counter() - t0
        finally:
            srv.close()
        expected = clients * requests_per_client
        return {
            "requests": results["sent"], "ok": results["ok"],
            "failed": results["failed"],
            "dropped": expected - results["sent"],
            "swaps_during": swaps,
            "model_seqs_served": sorted(seqs_served),
            "reqs_per_s": round(results["sent"] / elapsed, 1),
        }


# -- commit→served staleness under a cadence ----------------------------------


def run_staleness_segment(*, commits: int, cadence_s: float,
                          n_leaves: int, leaf_elems: int) -> dict:
    """Timed commit+publish cadence on one side, a store-watch registry
    polling on the other; staleness = adopted_at − publish time."""
    with tempfile.TemporaryDirectory(prefix="hvd_serving_bench_") as d:
        state = ObjectState(commit_dir=d, commit_async=False,
                            **_leaves(n_leaves, leaf_elems, 0, "frozen"))
        pub = Publisher(d, every=1, counters=_counters_clean)
        reg = ModelRegistry(store=pub.store)
        stop = threading.Event()
        staleness: List[float] = []
        seen = set()

        def watch():
            while not stop.is_set():
                if reg.poll_store(pub.store):
                    cur = reg.current()
                    if cur.manifest_seq not in seen:
                        seen.add(cur.manifest_seq)
                        staleness.append(
                            cur.adopted_at - cur.record["time"])
                time.sleep(0.01)

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        for gen in range(1, commits + 1):
            for k, v in _leaves(n_leaves, leaf_elems, gen,
                                "frozen").items():
                setattr(state, k, v)
            state.commit()
            pub.maybe_publish(state._commit_seq)
            time.sleep(cadence_s)
        # One cadence of grace for the last adoption, then stop.
        deadline = time.time() + max(2 * cadence_s, 1.0)
        while len(seen) < commits and time.time() < deadline:
            time.sleep(0.01)
        stop.set()
        w.join(timeout=10)
        return {
            "commits": commits, "cadence_s": cadence_s,
            "adopted": len(seen),
            "staleness_p50_s": round(statistics.median(staleness), 4)
            if staleness else None,
            "staleness_max_s": round(max(staleness), 4)
            if staleness else None,
        }


# -- overload shed probe (ISSUE 19 satellite) ---------------------------------


def run_shed_probe(*, burst: int = 16, queue_max: int = 2,
                   service_s: float = 0.15) -> dict:
    """Induced overload against one :class:`InferenceServer`: pin the
    admission queue to ``queue_max``, hold the batch worker busy
    (``service_s`` per forward), and land a ``burst`` of concurrent
    requests. The contract under fire: some requests MUST shed (429 +
    ``Retry-After`` — the queue is tiny), every accepted request MUST
    complete ok, and nothing may hang or 500. ``shed_fraction`` is the
    measured baseline the fleet bench (benchmarks/fleet.py) builds on.
    """
    saved = {k: os.environ.get(k) for k in
             (SC.QUEUE_MAX_ENV, SC.SHED_RETRY_AFTER_ENV)}
    os.environ[SC.QUEUE_MAX_ENV] = str(queue_max)
    os.environ[SC.SHED_RETRY_AFTER_ENV] = "0.05"
    try:
        with tempfile.TemporaryDirectory(prefix="hvd_shed_probe_") as d:
            state = ObjectState(commit_dir=d, commit_async=False,
                                **_leaves(2, 64, 0, "frozen"))
            pub = Publisher(d, every=1, counters=_counters_clean)
            reg = ModelRegistry(store=pub.store)
            state.commit()
            reg.adopt(pub.maybe_publish(state._commit_seq))

            def forward(payload, inputs, padded_n):
                time.sleep(service_s)
                return [float(q["x"]) for q in inputs]

            srv = InferenceServer(reg, forward, window_s=0.002,
                                  request_timeout_s=30.0)
            results = {"attempted": 0, "accepted": 0, "shed": 0,
                       "failed": 0}
            retry_afters: List[float] = []
            lock = threading.Lock()
            barrier = threading.Barrier(burst + 1)

            def one_request(i: int) -> None:
                body = json.dumps({"x": float(i)}).encode()
                req = urllib.request.Request(
                    f"http://{srv.addr()}/predict", data=body,
                    headers={"Content-Type": "application/json"})
                barrier.wait()
                # Tiny stagger: HTTPServer's listen backlog is 5, so a
                # perfectly simultaneous burst can get connects RESET at
                # the socket — a transport artifact, not a shed. Spread
                # the connects (the queue still overflows: service_s per
                # batch dwarfs the whole spread) and retry one reset.
                time.sleep(i * 0.002)
                outcome, ra = "failed", None
                for attempt in range(2):
                    try:
                        with urllib.request.urlopen(req, timeout=30) as r:
                            out = json.loads(r.read())
                        if out.get("ok") and out.get("result") == float(i):
                            outcome = "accepted"
                        break
                    except urllib.error.HTTPError as e:
                        e.read()
                        if e.code == 429:
                            outcome = "shed"
                            try:
                                ra = float(e.headers.get("Retry-After"))
                            except (TypeError, ValueError):
                                pass
                        break
                    except OSError:
                        continue
                with lock:
                    results["attempted"] += 1
                    results[outcome] += 1
                    if ra is not None:
                        retry_afters.append(ra)

            try:
                # Occupy the worker first so the burst meets a busy
                # server, then release the whole burst at once — the
                # tiny queue admits ~queue_max of it, sheds the rest.
                seed = threading.Thread(
                    target=lambda: urllib.request.urlopen(
                        urllib.request.Request(
                            f"http://{srv.addr()}/predict",
                            data=json.dumps({"x": -1.0}).encode(),
                            headers={"Content-Type": "application/json"}),
                        timeout=30).read(), daemon=True)
                seed.start()
                time.sleep(service_s / 3)   # seed is mid-forward
                threads = [threading.Thread(target=one_request, args=(i,),
                                            daemon=True)
                           for i in range(burst)]
                for t in threads:
                    t.start()
                barrier.wait()
                for t in threads:
                    t.join(timeout=60)
                seed.join(timeout=60)
            finally:
                srv.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "burst": burst, "queue_max": queue_max,
        "service_s": service_s,
        **results,
        "shed_fraction": round(results["shed"]
                               / max(results["attempted"], 1), 4),
        "retry_after_advertised_s": max(retry_afters)
        if retry_afters else None,
    }


# -- continuous decode vs bucketed full-forward (ISSUE 13) --------------------


def _llama_decode_fixture():
    """(cfg, model, unboxed params) for the decode arms — llama_tiny, the
    CPU-mesh workhorse of the parity tests."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from horovod_tpu.models.llama import Llama, llama_tiny

    cfg = llama_tiny()
    model = Llama(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    return cfg, model, nn.meta.unbox(variables)["params"]


def run_decode_segment(*, rounds: int = 5, slots: int = 8,
                       s_short: int = 4, s_long: int = 16) -> dict:
    """Interleaved A/B: one engine tick (``decode8`` — S new tokens via
    the paged-KV decode program) vs one bucketed full-forward serving
    step (``full8`` — the same S next-tokens recomputed from scratch on
    the padded [S, bucket] batch, the /predict-style baseline).

    Workload: ``slots`` concurrent sequences, 16-token prompt, a
    48-token generation budget — so the full-forward arm pads to the
    64 bucket (it must reserve prompt+max_new up front), while the
    decode arm's gather width is its per-slot context, sized for the
    whole timing run and therefore LARGER than 64: the ratio is
    conservative against the decode arm.
    """
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serving.decode import DecodeEngine

    cfg, model, params = _llama_decode_fixture()
    bs = 16
    prompt = list(range(1, 17))
    # Context budget: pre-warm + slope warmup + every timed round, with
    # one spare block so table growth never stalls mid-measurement.
    steps_budget = 1 + (rounds + 1) * (s_short + s_long) + s_long
    ctx_blocks = (len(prompt) + steps_budget) // bs + 2
    eng = DecodeEngine(cfg, params=params, slots=slots, block_size=bs,
                       pool_blocks=slots * ctx_blocks + 2,
                       max_blocks_per_slot=ctx_blocks,
                       prefill_buckets=(len(prompt),),
                       swap_policy="refill")
    max_new = ctx_blocks * bs - len(prompt)
    reqs = [eng.submit(prompt, max_new) for _ in range(slots)]
    eng.decode_once()               # admits all slots (prefill compiles)
    ttfts = sorted(r.ttft_s for r in reqs if r.ttft_s is not None)
    # TTFT split (ISSUE 16 satellite): time queued awaiting a slot vs
    # the prefill wall itself — ttft ≈ queue_wait + prefill_wall, and
    # only the second half is the model's bill.
    qwaits = sorted(r.queue_wait_s for r in reqs
                    if r.queue_wait_s is not None)
    pwalls = sorted(r.prefill_wall_s for r in reqs
                    if r.prefill_wall_s is not None)

    full_seq = 64                   # bucket for prompt 16 + max_new 48
    full_toks = jnp.zeros((slots, full_seq), jnp.int32)
    full_toks = full_toks.at[:, :len(prompt)].set(
        jnp.asarray(prompt, jnp.int32))

    @jax.jit
    def _full_step(p, toks):
        logits = model.apply({"params": p}, toks)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    def run_full(k):
        out = None
        for _ in range(k):
            out = _full_step(params, full_toks)
        common.sync(out)

    def run_decode(k):
        for _ in range(k):
            eng.decode_once()
        common.sync(eng._dev_tokens)

    run_decode(1)                   # decode program compiled here
    run_full(1)
    warm = dict(eng.compile_counts)
    slopes, rnds = common.slope_time_paired(
        {"full8": run_full, "decode8": run_decode},
        s_short, s_long, rounds=rounds, return_rounds=True)
    steady_compiles = eng.compile_counts["decode"] - warm["decode"]
    ratios = [r["full8"] / r["decode8"] for r in rnds]
    swap = _run_swap_probe(cfg, params, slots=slots)
    shed = run_shed_probe()
    return {
        "model": "llama_tiny", "slots": slots, "block_size": bs,
        "devices_used": 1, "prompt_len": len(prompt),
        "full_arm_seq": full_seq,
        "sec_per_step": {k: round(v, 6) for k, v in slopes.items()},
        "decode_tokens_per_s_per_chip": round(slots / slopes["decode8"], 1),
        "speedup_vs_full": round(common.median_ratio(
            rnds, "full8", "decode8"), 4),
        "noise": _noise(ratios),
        "ttft_p50_s": round(statistics.median(ttfts), 6) if ttfts else None,
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 6)
        if ttfts else None,
        "ttft_max_s": round(ttfts[-1], 6) if ttfts else None,
        "queue_wait_p50_s": round(statistics.median(qwaits), 6)
        if qwaits else None,
        "queue_wait_p99_s": round(float(np.percentile(qwaits, 99)), 6)
        if qwaits else None,
        "prefill_wall_p50_s": round(statistics.median(pwalls), 6)
        if pwalls else None,
        "prefill_wall_p99_s": round(float(np.percentile(pwalls, 99)), 6)
        if pwalls else None,
        "steady_decode_compiles": steady_compiles,
        "compile_counts": dict(eng.compile_counts),
        "swap": swap,
        "shed_fraction": shed["shed_fraction"],
        "shed": shed,
    }


def _run_swap_probe(cfg, params, *, slots: int, steps: int = 60,
                    swap_at=(20, 40)) -> dict:
    """Per-step decode latency while weight hot-swaps land mid-decode
    under the refill policy (live block tables remapped via re-prefill).
    Prefill buckets are pre-warmed so p99 charges the swap, not XLA."""
    import time as _time

    from horovod_tpu.serving.decode import DecodeEngine

    bs = 16
    eng = DecodeEngine(cfg, params=params, slots=slots, block_size=bs,
                       pool_blocks=slots * 8 + 2, max_blocks_per_slot=8,
                       prefill_buckets=(16, 32, 64), swap_policy="refill")
    # Warm every prefill bucket with throwaway one-token requests so the
    # mid-decode refill (which re-prefills at the sequence's bucket)
    # never hits a compile inside a timed step.
    for warm_len in (16, 20, 40):
        eng.submit(list(range(1, warm_len + 1)), 1)
        eng.decode_once()
    prompt = list(range(1, 17))
    reqs = [eng.submit(prompt, 8 * bs - len(prompt))
            for _ in range(slots)]
    eng.decode_once()               # admit + first decode step
    warm_decode = eng.compile_counts["decode"]
    walls = []
    for step in range(steps):
        if step in swap_at:
            # Re-install = new manifest seq: observed as a hot-swap.
            eng.install_params(params)
        t0 = _time.perf_counter()
        eng.decode_once()
        common.sync(eng._dev_tokens)  # hvd-analyze: ok — latency probe
        walls.append(_time.perf_counter() - t0)
    truncated = sum(1 for r in reqs if r.truncated)
    return {
        "policy": "refill", "steps": steps,
        "swaps_during": len(swap_at),
        "p50_step_s": round(float(np.percentile(walls, 50)), 6),
        "p99_step_s": round(float(np.percentile(walls, 99)), 6),
        "truncated": truncated,
        "steady_decode_compiles":
            eng.compile_counts["decode"] - warm_decode,
    }


# -- sharded decode: tp scaling + per-shard swap bytes (ISSUE 14) -------------


def _serve_decode_fixture(kind: str):
    """(cfg, params-factory) at SERVE scale — FFN/attention weights
    dominate the replicated embeddings/norms, the regime sharded serving
    targets (at tiny scale the replicated vocab leaves would dominate
    the swap-bytes ratio and say nothing about the feature)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    if kind == "llama":
        from horovod_tpu.models.llama import Llama, llama_tiny
        cfg = dataclasses.replace(llama_tiny(), dim=256, hidden_dim=2048,
                                  n_layers=3, n_heads=8, n_kv_heads=8)
        model = Llama(cfg)
    else:
        from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny
        cfg = dataclasses.replace(mixtral_tiny(), dim=256, hidden_dim=512,
                                  n_layers=2, n_heads=8, n_kv_heads=8,
                                  capacity_factor=8.0)
        model = Mixtral(cfg)

    def mkparams(seed: int = 0):
        return nn.meta.unbox(jax.jit(model.init)(
            jax.random.PRNGKey(seed),
            jnp.zeros((1, 16), jnp.int32)))["params"]

    return cfg, mkparams


#: The 8 "devices" of the CPU mesh timeshare ONE core, so raw wall-clock
#: cannot show tp speedup (CLAUDE.md: ratios under one harness). The
#: normalized figure credits each arm with hardware concurrency:
#: wall/devices ≈ per-device busy time here, so slots·devices/wall is
#: the tokens/s an actually-parallel tp mesh delivers at these walls.
NORMALIZED_UNIT = ("tokens per device-time second: slots*devices/wall; "
                   "the CPU mesh's N virtual devices timeshare one core, "
                   "so wall ~= N x per-device time")


def run_sharded_decode_segment(*, rounds: int = 4, base_slots: int = 4,
                               s_short: int = 3, s_long: int = 9,
                               tps=(1, 4, 8)) -> dict:
    """Paired tp=1 vs tp=4/8 decode arms for BOTH LLMs at a fixed
    per-device KV budget: the tp arm shards the pool over heads (1/tp
    bytes per device) and spends the headroom on tp× slots — the
    capacity scaling ROADMAP 3(a) asks serving to buy with more chips.
    All arms ride inside every ``slope_time_paired`` round; scaling is
    the median of per-round normalized-tokens/s ratios. Windows are
    longer than the decode segment's (3/9-step pairs, 4 rounds) to hold
    the mixtral ratio's relative spread under the guardrail ceiling —
    see the module docstring's noise-band note."""
    import jax

    from horovod_tpu.parallel import create_mesh
    from horovod_tpu.serving.decode import DecodeEngine

    bs = 16
    prompt = list(range(1, 17))
    steps_budget = 1 + (rounds + 1) * (s_short + s_long) + s_long
    ctx_blocks = (len(prompt) + steps_budget) // bs + 2

    def _make_run(e):
        def run(k):
            for _ in range(k):
                e.decode_once()
            common.sync(e._dev_tokens)       # once, AFTER the timed window
        return run

    models: Dict[str, dict] = {}
    for kind in ("llama", "mixtral"):
        cfg, mkparams = _serve_decode_fixture(kind)
        params = mkparams(0)
        engines: Dict[int, object] = {}
        runners: Dict[str, object] = {}
        for tp in tps:
            mesh = None if tp <= 1 else create_mesh(
                {"tp": tp}, devices=jax.devices()[:tp])
            slots = base_slots * tp
            eng = DecodeEngine(cfg, params=params, slots=slots,
                               block_size=bs,
                               pool_blocks=slots * ctx_blocks + 2,
                               max_blocks_per_slot=ctx_blocks,
                               prefill_buckets=(len(prompt),),
                               swap_policy="refill", mesh=mesh)
            max_new = ctx_blocks * bs - len(prompt)
            for _ in range(slots):
                eng.submit(prompt, max_new)
            run = _make_run(eng)
            run(1)                  # admit all slots; compiles both
            engines[tp] = eng       # programs before the timed rounds
            runners[f"tp{tp}"] = run
        warm = {tp: engines[tp].compile_counts["decode"] for tp in tps}
        slopes, rnds = common.slope_time_paired(
            runners, s_short, s_long, rounds=rounds, return_rounds=True)
        steady = {f"tp{tp}": engines[tp].compile_counts["decode"] - warm[tp]
                  for tp in tps}

        def _norm(tp, wall):
            return base_slots * tp * tp / wall      # slots(tp) * devices

        scaling, noise = {}, {}
        for tp in tps:
            if tp == 1:
                continue
            ratios = sorted(_norm(tp, r[f"tp{tp}"]) / _norm(1, r["tp1"])
                            for r in rnds)
            scaling[f"tp{tp}_vs_tp1"] = round(
                statistics.median(ratios), 4)
            noise[f"tp{tp}_vs_tp1"] = _noise(ratios)
        models[kind] = {
            "slots": {f"tp{tp}": base_slots * tp for tp in tps},
            "sec_per_step": {k: round(v, 6) for k, v in slopes.items()},
            "tokens_per_s_raw": {
                f"tp{tp}": round(base_slots * tp / slopes[f"tp{tp}"], 1)
                for tp in tps},
            "tokens_per_s_normalized": {
                f"tp{tp}": round(_norm(tp, slopes[f"tp{tp}"]), 1)
                for tp in tps},
            "scaling_normalized": scaling,
            "noise": noise,
            "steady_decode_compiles": steady,
            "swap_bytes": _run_shard_swap_bytes(mkparams),
        }
        del engines, runners
    return {
        "devices": len(jax.devices()),
        "base_slots": base_slots,
        "block_size": bs,
        "ctx_blocks_per_slot": ctx_blocks,
        "normalized_unit": NORMALIZED_UNIT,
        "models": models,
    }


def _run_shard_swap_bytes(mkparams, tps=(4, 8)) -> dict:
    """All-leaves hot-swap cost per replica host: a shard-selecting
    registry (per-shard CAS) vs a whole-leaf registry adopting the SAME
    publish. Bytes are deterministic — no timing, no interleaving
    needed; the rail is replica <= full/tp * 1.25."""
    import jax

    from horovod_tpu.serving.decode import tp_shard_plan, tp_shard_selector

    out = {}
    for tp in tps:
        with tempfile.TemporaryDirectory(prefix="hvd_shard_swap_") as d:
            state = ObjectState(
                commit_dir=d, commit_async=False,
                params=jax.tree.map(np.asarray, mkparams(0)))
            pub = Publisher(d, every=1, counters=_counters_clean,
                            shard_plan=tp_shard_plan(tp))
            full = ModelRegistry(store=pub.store)
            replica = ModelRegistry(store=pub.store,
                                    shard_selector=tp_shard_selector(tp, 0))
            state.commit()
            rec = pub.maybe_publish(state._commit_seq)
            assert rec is not None and full.adopt(rec) and replica.adopt(rec)
            f0 = full.stats["bytes_fetched"]
            r0 = replica.stats["bytes_fetched"]
            state.params = jax.tree.map(np.asarray, mkparams(1))
            state.commit()                          # every leaf changed
            rec = pub.maybe_publish(state._commit_seq)
            assert rec is not None and full.adopt(rec) and replica.adopt(rec)
            fb = full.stats["bytes_fetched"] - f0
            rb = replica.stats["bytes_fetched"] - r0
            out[f"tp{tp}"] = {
                "full_leaf_bytes": int(fb),
                "replica_bytes": int(rb),
                "ratio_full_over_replica": round(fb / max(rb, 1), 3),
                "ceiling_bytes": int(fb / tp * SHARD_SWAP_SLACK),
            }
    return out


# -- speculative decode vs plain one-token decode (ISSUE 16) ------------------


def run_spec_decode_segment(*, rounds: int = 6, slots: int = 4,
                            spec_k: int = 4, ctx_blocks: int = 192,
                            window_ticks: int = 25,
                            warm_tokens: int = 40) -> dict:
    """Paired spec-vs-plain tokens/s on two workloads (module docstring):
    repeat_heavy (built-in n-gram drafter, settled acceptance ~2
    tokens/slot/tick on the periodic stream) and adversarial (random
    prompts + an injected always-wrong drafter, acceptance 0 — the
    lossless floor). The arms run at a LONG context provision
    (``ctx_blocks=192`` → 3072-position tables): speculative decode
    targets memory-bound decode, and on this tiny model the shared
    KV-gather only dominates the per-token weight math once the table
    is wide — at 320 positions the k-wide verify costs ~10% more than
    a decode tick, at 2048–3072 the two walls are equal, which is the
    regime the adversarial floor is honest in. Wider is NOT better
    past that: at 3584+ positions the k-row verify's working set falls
    out of cache while the 1-row decode's still fits (measured
    adversarial 0.99 → 0.60 between 192 and 224 blocks — a real cache
    cliff, not noise). Both arms of a pair see the SAME width, so the
    A/B stays fair at any provision.

    Because spec emits a VARIABLE number of tokens per tick, two-window
    slope pairing breaks (the token delta and the wall delta fluctuate
    independently — measured negative slopes); each arm's figure is
    instead a plain rate over one ~25-tick window, synced at both
    edges, all four engines interleaved inside every round. Every
    engine is first warmed past the ~25-token aperiodic transient the
    repeat prompt emits before its stream settles (``warm_tokens``),
    so the n-gram drafter is measured in its steady acceptance regime."""
    import gc

    from horovod_tpu.serving.decode import DecodeEngine

    # Collect any pending garbage before the timed windows: the spec
    # arm syncs every tick, so a deferred free landing mid-window hits
    # it ~25× harder than the once-per-window-synced plain arm.
    gc.collect()
    cfg, model, params = _llama_decode_fixture()
    bs = 16
    vocab = int(cfg.vocab_size)
    prompt_len = 16
    # Token budget: warm + every measured window at full acceptance
    # must finish without any slot retiring mid-measurement.
    max_new = ctx_blocks * bs - prompt_len - (spec_k - 1)
    need = warm_tokens + (rounds + 1) * window_ticks * spec_k
    assert need < max_new, (need, max_new)

    repeat_prompt = [3, 5, 7, 9] * (prompt_len // 4)
    rng = np.random.RandomState(7)
    adv_prompts = [[int(t) for t in rng.randint(0, vocab, size=prompt_len)]
                   for _ in range(slots)]

    def pessimal_draft(ctx, n):
        # Deterministically wrong against any stream the model emits at
        # that position EXCEPT a coincidental last+1 — rejection rate is
        # ~1 and the engine's guaranteed token is the only progress.
        return [(int(ctx[-1]) + 1) % vocab] * n

    def mk_engine(k, draft_fn=None):
        return DecodeEngine(cfg, params=params, slots=slots,
                            block_size=bs,
                            pool_blocks=slots * ctx_blocks + 2,
                            max_blocks_per_slot=ctx_blocks,
                            prefill_buckets=(prompt_len,),
                            swap_policy="refill", spec_k=k,
                            draft_fn=draft_fn)

    arms = {
        "repeat_heavy": {"plain": mk_engine(0), "spec": mk_engine(spec_k)},
        "adversarial": {"plain": mk_engine(0),
                        "spec": mk_engine(spec_k,
                                          draft_fn=pessimal_draft)},
    }
    for name, pair in arms.items():
        prompts = [repeat_prompt] * slots if name == "repeat_heavy" \
            else adv_prompts
        for eng in pair.values():
            for p in prompts:
                eng.submit(list(p), max_new)

    ticks = {(n, a): 0 for n in arms for a in ("plain", "spec")}

    def _sync(eng):
        # Spec ticks already synced on the host token fetch; the plain
        # arm syncs its device token refs.
        common.sync(eng._kp if eng.spec_k else eng._dev_tokens)

    # Warm-in: compile (admit + first ticks) AND run past the repeat
    # stream's aperiodic transient so the drafter is measured settled.
    for name, pair in arms.items():
        for arm, eng in pair.items():
            while min(s.gen for s in eng.slots) < warm_tokens:
                eng.decode_once()
                ticks[(name, arm)] += 1
            _sync(eng)
    warm = {(n, a): dict(e.compile_counts)
            for n, pair in arms.items() for a, e in pair.items()}

    def token_rate(name, arm):
        eng = arms[name][arm]
        _sync(eng)
        t0, e0 = time.perf_counter(), eng.tokens_emitted
        for _ in range(window_ticks):
            eng.decode_once()
        _sync(eng)
        ticks[(name, arm)] += window_ticks
        return (eng.tokens_emitted - e0) / max(
            time.perf_counter() - t0, 1e-9)

    per_round: Dict[str, List[dict]] = {n: [] for n in arms}
    for _ in range(rounds):
        # Interleaved: all four arms inside every round, so drift hits
        # them alike (CLAUDE.md: ratios, never separate blocks).
        for name in arms:
            tps = {a: token_rate(name, a) for a in ("plain", "spec")}
            per_round[name].append(
                {**tps, "ratio": tps["spec"] / max(tps["plain"], 1e-9)})

    out_arms = {}
    for name, pair in arms.items():
        ratios = sorted(r["ratio"] for r in per_round[name])
        steady = {a: sum(e.compile_counts.get(prog, 0)
                         - warm[(name, a)].get(prog, 0)
                         for prog in set(e.compile_counts)
                         | set(warm[(name, a)]))
                  for a, e in pair.items()}
        spec_eng = pair["spec"]
        out_arms[name] = {
            "tokens_per_s": {
                a: round(statistics.median(
                    r[a] for r in per_round[name]), 1)
                for a in ("plain", "spec")},
            "speedup": round(statistics.median(ratios), 4),
            "noise": _noise(ratios),
            "spec_tokens_per_tick": round(
                spec_eng.tokens_emitted / max(ticks[(name, "spec")], 1), 3),
            "compile_counts": {a: dict(e.compile_counts)
                               for a, e in pair.items()},
            "steady_compiles": steady,
        }
    return {
        "model": "llama_tiny", "slots": slots, "spec_k": spec_k,
        "block_size": bs, "ctx_blocks": ctx_blocks,
        "window_ticks": window_ticks, "rounds": rounds,
        "prompt_len": prompt_len, "arms": out_arms,
    }


# -- aggregation --------------------------------------------------------------


def _noise(ratios: List[float]) -> dict:
    rs = sorted(ratios)
    return {"rounds": len(rs),
            "ratio_min": round(rs[0], 4),
            "ratio_max": round(rs[-1], 4),
            "spread": round(rs[-1] - rs[0], 4)}


def run_harness(*, rounds: int, swaps: int, n_leaves: int,
                leaf_elems: int) -> dict:
    # The spec segment runs FIRST: its spec arm syncs the device every
    # tick (acceptance needs the [S, k] fetch), so it is the segment
    # most sensitive to process state the others leave behind (compiled
    # mixtral tp8 programs, server/poll threads, deferred frees — the
    # first full-harness run measured the same arms ~0.07 lower than
    # standalone). Measuring it on the fresh process keeps the ratio
    # honest; the other segments sync once per window and don't care.
    spec = run_spec_decode_segment(rounds=max(6, rounds + 1))
    arms: Dict[str, List[dict]] = {"all": [], "frozen": []}
    pair_ratios: List[float] = []
    for _ in range(rounds):
        # Interleaved: all then frozen inside every round pair, so drift
        # (CPU load, page cache) hits both arms alike.
        a = run_swap_round("all", swaps=swaps, n_leaves=n_leaves,
                           leaf_elems=leaf_elems)
        f = run_swap_round("frozen", swaps=swaps, n_leaves=n_leaves,
                           leaf_elems=leaf_elems)
        arms["all"].append(a)
        arms["frozen"].append(f)
        pair_ratios.append(a["adopt_s_median"]
                           / max(f["adopt_s_median"], 1e-9))
    traffic = run_traffic_segment(swaps=2, n_leaves=n_leaves,
                                  leaf_elems=leaf_elems)
    stale = run_staleness_segment(commits=5, cadence_s=0.2,
                                  n_leaves=n_leaves, leaf_elems=leaf_elems)
    decode = run_decode_segment(rounds=rounds)
    sharded = run_sharded_decode_segment(rounds=max(4, rounds - 1))

    def med(mode: str, field: str) -> float:
        return round(statistics.median(
            r[field] for r in arms[mode]), 6)

    return {
        "bench": "serving",
        "rounds": rounds, "swaps": swaps, "n_leaves": n_leaves,
        "leaf_kb": arms["all"][0]["leaf_kb"],
        "adopt_s": {m: med(m, "adopt_s_median") for m in ("all", "frozen")},
        # Headline: all/frozen adopt-wall ratio, median over interleaved
        # round pairs — the delta-fetch advantage.
        "swap_ratio": round(statistics.median(pair_ratios), 4),
        "noise": _noise(pair_ratios),
        "blobs_fetched_per_swap": {
            m: med(m, "blobs_fetched_per_swap") for m in ("all", "frozen")},
        "leaves_reused_per_swap": {
            m: med(m, "leaves_reused_per_swap") for m in ("all", "frozen")},
        "traffic": traffic,
        "staleness": stale,
        "decode": decode,
        "sharded_decode": sharded,
        "spec_decode": spec,
    }


def _append_history(rec: dict) -> None:
    import datetime
    import subprocess
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=os.path.dirname(HISTORY_PATH)
                             ).stdout.strip() or None
    except OSError:
        sha = None
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    with open(HISTORY_PATH, "a", encoding="utf-8") as fh:
        fh.write(json.dumps({"date": stamp, "git": sha, **rec}) + "\n")


# -- --check: guardrail over the recorded series ------------------------------


def check_history(path: str = HISTORY_PATH) -> dict:
    """Validate the NEWEST history record: the keys the guardrail test
    pins must exist and sit inside the rails."""
    with open(path, "r", encoding="utf-8") as fh:
        recs = [json.loads(line) for line in fh if line.strip()]
    recs = [r for r in recs if r.get("bench") == "serving"]
    if not recs:
        raise ValueError(f"no serving records in {path}")
    rec = recs[-1]
    problems: List[str] = []

    def need(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)

    ratio = rec.get("swap_ratio")
    need(isinstance(ratio, (int, float)) and ratio >= MIN_SWAP_RATIO,
         f"swap_ratio={ratio} < {MIN_SWAP_RATIO}x (frozen-leaf swap not "
         f"strictly cheaper than all-leaves)")
    noise = rec.get("noise") or {}
    need(noise.get("rounds", 0) >= 2
         and all(k in noise for k in ("ratio_min", "ratio_max", "spread")),
         f"noise band incomplete: {noise}")
    fetched = rec.get("blobs_fetched_per_swap") or {}
    need(isinstance(fetched.get("frozen"), (int, float))
         and isinstance(fetched.get("all"), (int, float))
         and fetched["frozen"] < fetched["all"],
         f"frozen arm did not fetch fewer blobs per swap: {fetched}")
    traffic = rec.get("traffic") or {}
    need(traffic.get("requests", 0) > 0 and traffic.get("dropped") == 0
         and traffic.get("failed") == 0,
         f"traffic lost requests across swaps: {traffic}")
    need(traffic.get("swaps_during", 0) >= 2
         and len(traffic.get("model_seqs_served") or []) >= 2,
         f"traffic did not span >=2 hot-swaps: {traffic}")
    stale = rec.get("staleness") or {}
    need(stale.get("adopted") == stale.get("commits"),
         f"staleness segment missed publishes: {stale}")
    smax = stale.get("staleness_max_s")
    need(isinstance(smax, (int, float)) and 0 < smax < MAX_STALENESS_S,
         f"staleness_max_s={smax} outside (0, {MAX_STALENESS_S})")
    dec = rec.get("decode") or {}
    spd = dec.get("speedup_vs_full")
    need(isinstance(spd, (int, float)) and spd >= MIN_DECODE_SPEEDUP,
         f"decode speedup_vs_full={spd} < {MIN_DECODE_SPEEDUP}x (continuous "
         f"decode not beating bucketed full-forward serving)")
    tps = dec.get("decode_tokens_per_s_per_chip")
    need(isinstance(tps, (int, float)) and tps > 0,
         f"decode tokens/s/chip missing or non-positive: {tps}")
    need(dec.get("steady_decode_compiles") == 0,
         f"decode recompiled in steady state: "
         f"steady_decode_compiles={dec.get('steady_decode_compiles')}")
    dnoise = dec.get("noise") or {}
    need(dnoise.get("rounds", 0) >= 3
         and all(k in dnoise for k in ("ratio_min", "ratio_max", "spread")),
         f"decode noise band incomplete: {dnoise}")
    ttft = dec.get("ttft_p50_s")
    need(isinstance(ttft, (int, float)) and ttft > 0,
         f"decode ttft_p50_s missing or non-positive: {ttft}")
    ttft99 = dec.get("ttft_p99_s")
    need(isinstance(ttft99, (int, float)) and ttft99 >= ttft,
         f"decode ttft_p99_s missing or below p50: {ttft99}")
    # TTFT split: queue wait + prefill wall must both be recorded (the
    # split is the actionable figure — which half of TTFT to attack).
    for field in ("queue_wait_p50_s", "queue_wait_p99_s",
                  "prefill_wall_p50_s", "prefill_wall_p99_s"):
        v = dec.get(field)
        need(isinstance(v, (int, float)) and v >= 0,
             f"decode {field} missing or negative: {v}")
    pw = dec.get("prefill_wall_p50_s")
    need(isinstance(pw, (int, float)) and pw > 0,
         f"decode prefill_wall_p50_s must be positive: {pw}")
    dswap = dec.get("swap") or {}
    p99 = dswap.get("p99_step_s")
    need(dswap.get("swaps_during", 0) >= 2
         and isinstance(p99, (int, float)) and 0 < p99 < MAX_DECODE_P99_S
         and dswap.get("steady_decode_compiles") == 0,
         f"decode swap probe incomplete or out of rails: {dswap}")
    # Shed probe (ISSUE 19 satellite): induced overload must actually
    # shed (fraction strictly inside (0, 1)), every accepted request
    # must come back ok, nothing may fail any other way, and the 429s
    # must advertise a Retry-After pace.
    sf = dec.get("shed_fraction")
    dshed = dec.get("shed") or {}
    need(isinstance(sf, (int, float)) and 0 < sf < 1,
         f"decode shed_fraction={sf} outside (0, 1) — overload probe "
         f"did not exercise the shedding path")
    need(dshed.get("failed") == 0
         and dshed.get("accepted", 0) > 0
         and dshed.get("accepted", 0) + dshed.get("shed", 0)
         == dshed.get("attempted"),
         f"shed probe lost requests (accepted+shed != attempted, or "
         f"failures): {dshed}")
    ra = dshed.get("retry_after_advertised_s")
    need(isinstance(ra, (int, float)) and ra > 0,
         f"shed probe 429s carried no Retry-After: {ra}")
    shd = rec.get("sharded_decode") or {}
    need(isinstance(shd.get("normalized_unit"), str)
         and "timeshare" in shd.get("normalized_unit", ""),
         "sharded_decode normalized_unit missing (the device-time "
         "normalization must be declared, not implied)")
    smodels = shd.get("models") or {}
    need(set(smodels) >= {"llama", "mixtral"},
         f"sharded_decode must cover both LLMs, got {sorted(smodels)}")
    for kind, m in sorted(smodels.items()):
        sc = (m.get("scaling_normalized") or {}).get("tp8_vs_tp1")
        need(isinstance(sc, (int, float)) and sc >= MIN_TP8_SCALING,
             f"{kind} sharded decode tp8_vs_tp1={sc} < {MIN_TP8_SCALING}x")
        snoise = (m.get("noise") or {}).get("tp8_vs_tp1") or {}
        need(snoise.get("rounds", 0) >= 3,
             f"{kind} sharded scaling noise band incomplete: {snoise}")
        compiles = m.get("steady_decode_compiles") or {}
        need(compiles and all(v == 0 for v in compiles.values()),
             f"{kind} sharded decode recompiled in steady state: "
             f"{compiles}")
        for arm, sw in sorted((m.get("swap_bytes") or {}).items()):
            tp = int(arm[2:])
            rb, fb = sw.get("replica_bytes"), sw.get("full_leaf_bytes")
            need(isinstance(rb, int) and isinstance(fb, int) and 0 < rb
                 and rb <= fb / tp * SHARD_SWAP_SLACK,
                 f"{kind} {arm} replica swap bytes {rb} exceed "
                 f"{SHARD_SWAP_SLACK}x the 1/{tp} share of full-leaf "
                 f"bytes {fb}")
        need(len(m.get("swap_bytes") or {}) >= 2,
             f"{kind} swap_bytes must cover tp=4 and tp=8")
    spec = rec.get("spec_decode") or {}
    need(isinstance(spec.get("spec_k"), int) and spec.get("spec_k", 0) >= 2,
         f"spec_decode spec_k missing or < 2: {spec.get('spec_k')}")
    sarms = spec.get("arms") or {}
    need(set(sarms) >= {"repeat_heavy", "adversarial"},
         f"spec_decode must cover both workloads, got {sorted(sarms)}")
    floors = {"repeat_heavy": MIN_SPEC_REPEAT_SPEEDUP,
              "adversarial": MIN_SPEC_ADVERSARIAL_RATIO}
    for name, arm in sorted(sarms.items()):
        spd = arm.get("speedup")
        floor = floors.get(name, MIN_SPEC_ADVERSARIAL_RATIO)
        need(isinstance(spd, (int, float)) and spd >= floor,
             f"spec_decode {name} speedup={spd} < {floor}x plain")
        anoise = arm.get("noise") or {}
        need(anoise.get("rounds", 0) >= 3
             and all(k in anoise
                     for k in ("ratio_min", "ratio_max", "spread")),
             f"spec_decode {name} noise band incomplete: {anoise}")
        tps_arm = arm.get("tokens_per_s") or {}
        need(all(isinstance(tps_arm.get(a), (int, float))
                 and tps_arm.get(a, 0) > 0 for a in ("plain", "spec")),
             f"spec_decode {name} tokens/s missing: {tps_arm}")
        steady = arm.get("steady_compiles") or {}
        need(steady and all(v == 0 for v in steady.values()),
             f"spec_decode {name} recompiled in steady state: {steady}")
        counts = (arm.get("compile_counts") or {}).get("spec") or {}
        need(counts.get("verify") == 1 and counts.get("decode", 0) == 0,
             f"spec_decode {name} spec arm compile counts off (want one "
             f"verify, zero decode): {counts}")
    return {"check": "serving", "ok": not problems,
            "record_date": rec.get("date"), "record_git": rec.get("git"),
            "problems": problems}


# -- entry points -------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved all/frozen round pairs")
    ap.add_argument("--swaps", type=int, default=4,
                    help="timed hot-swaps per round")
    ap.add_argument("--leaves", type=int, default=16,
                    help="model leaves (one changes in the frozen arm)")
    ap.add_argument("--leaf-elems", type=int, default=65536,
                    help="float32 elements per leaf (256 KiB default)")
    ap.add_argument("--check", action="store_true",
                    help="validate the newest history record and exit")
    ap.add_argument("--smoke", type=int, default=0, metavar="N",
                    help="one shrunk round pair with N leaves (chaos "
                         "tier); prints that pair's JSON")
    a = ap.parse_args(argv)

    if a.check:
        verdict = check_history()
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1

    if a.smoke:
        res_all = run_swap_round("all", swaps=2, n_leaves=a.smoke,
                                 leaf_elems=4096)
        res_frz = run_swap_round("frozen", swaps=2, n_leaves=a.smoke,
                                 leaf_elems=4096)
        traffic = run_traffic_segment(swaps=2, n_leaves=a.smoke,
                                      leaf_elems=4096,
                                      clients=2, requests_per_client=10)
        print(json.dumps({"bench": "serving_smoke", "all": res_all,
                          "frozen": res_frz, "traffic": traffic}))
        ok = (traffic["dropped"] == 0 and traffic["failed"] == 0
              and res_frz["blobs_fetched_per_swap"]
              < res_all["blobs_fetched_per_swap"])
        return 0 if ok else 1

    rec = run_harness(rounds=a.rounds, swaps=a.swaps, n_leaves=a.leaves,
                      leaf_elems=a.leaf_elems)
    print(json.dumps(rec))
    if os.environ.get(NO_HISTORY_ENV, "").lower() not in ("1", "true"):
        _append_history(rec)
        dec = rec.get("decode") or {}
        if isinstance(dec.get("speedup_vs_full"), (int, float)):
            # Ratchet the decode win in perf_history too, so
            # `tools.perf check` rails it per (model, arm)
            # (respects HOROVOD_PERF_NO_HISTORY).
            from horovod_tpu.tools import perf as perf_tools
            perf_tools.append_history({
                "kind": "perf_ratio",
                "metric": "decode_speedup",
                "model": "llama_tiny_serve_cpu8",
                "arm": "continuous_decode_vs_full",
                "ratio": dec["speedup_vs_full"],
                "decode_tokens_per_s_per_chip":
                    dec.get("decode_tokens_per_s_per_chip"),
                "noise": dec.get("noise"),
            })
        shd = (rec.get("sharded_decode") or {}).get("models") or {}
        for kind, m in sorted(shd.items()):
            sc = (m.get("scaling_normalized") or {}).get("tp8_vs_tp1")
            if isinstance(sc, (int, float)):
                from horovod_tpu.tools import perf as perf_tools
                perf_tools.append_history({
                    "kind": "perf_ratio",
                    "metric": "sharded_decode_scaling",
                    "model": f"{kind}_serve_cpu8",
                    "arm": "tp8_vs_tp1_normalized",
                    "ratio": sc,
                    "tokens_per_s_normalized":
                        m.get("tokens_per_s_normalized"),
                    "noise": (m.get("noise") or {}).get("tp8_vs_tp1"),
                })
        spec = rec.get("spec_decode") or {}
        for arm_name, arm in sorted((spec.get("arms") or {}).items()):
            if isinstance(arm.get("speedup"), (int, float)):
                from horovod_tpu.tools import perf as perf_tools
                perf_tools.append_history({
                    "kind": "spec_decode",
                    "metric": "spec_decode_speedup",
                    "model": "llama_tiny_serve_cpu8",
                    "arm": arm_name,
                    "ratio": arm["speedup"],
                    "spec_k": spec.get("spec_k"),
                    "tokens_per_s": arm.get("tokens_per_s"),
                    "noise": arm.get("noise"),
                    "steady_compiles": sum(
                        (arm.get("steady_compiles") or {}).values()),
                })
    return 0


if __name__ == "__main__":
    sys.exit(main())
