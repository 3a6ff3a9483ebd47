"""Control-plane scale harness: one real coordinator, O(100-1000) fake
workers.

ROADMAP item 1 / ISSUE 7: the chaos tier proves the elastic stack
*correct* at np=3; this harness measures whether the coordinator
*survives* the north-star fleet. Workers here are cheap fake ranks — no
jax, no engine — just the control-plane lifecycle a real worker performs:
register, rendezvous on the first world publish, then watch ``/world``
for membership changes and failures.

A/B in ONE run (CLAUDE.md: interleaved rounds, ratios not absolutes —
never separate blocks): for each world size the harness alternates

- **legacy** rounds — the pre-PR wire protocol, pinned via
  ``CoordinatorClient(delta=False)``: per-worker registration (one
  journal fsync each) and cursorless interval polling where EVERY reply
  is the full world payload; and
- **delta** rounds — the pod-scale protocol: one ``register_batch`` per
  host (one fsync per host), cursor + versioned-delta replies, and
  bounded long-poll stretched to the server-advertised ``poll_s`` pacing
  (so a parked worker is woken by a change immediately, and steady-state
  aggregate request rate tracks ``HOROVOD_COORDINATOR_TARGET_RPS``
  instead of growing linearly with np).

Measured per (size, mode) round: rendezvous latency (first register →
every worker saw the v1 world), regrow latency (failure + shrunk-world
publish → every worker saw it), steady-state requests/s, response bytes
per membership change (ALL bytes a change costs, including the polls
between changes — redundant full payloads are exactly the legacy cost),
and journal bytes. A separate deterministic mutation-stream check proves
journal compaction preserves ``version``/``failure_seq`` (and the rest
of the state) byte-for-byte against an uncompacted replay, through a
simulated crash.

Emits one JSON line and appends it — stamped with
date + git SHA — to ``benchmarks/control_plane_history.jsonl`` unless
``HOROVOD_CONTROL_PLANE_NO_HISTORY`` is set. ``--check`` validates the
newest history record (presence + ranges); ``--smoke N`` runs
one delta round at N workers for the chaos-tier budget test.
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
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from horovod_tpu.elastic import constants as C                # noqa: E402
from horovod_tpu.elastic import journal as journal_mod        # noqa: E402
from horovod_tpu.elastic.service import (CoordinatorClient,   # noqa: E402
                                         CoordinatorService)
from horovod_tpu.runner import secret as _secret              # noqa: E402

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "control_plane_history.jsonl")
NO_HISTORY_ENV = "HOROVOD_CONTROL_PLANE_NO_HISTORY"

#: --check rails (HARD bands: fail only on movement no stated noise
#: explains).
MIN_BYTES_RATIO = 5.0        # acceptance: >=5x fewer bytes per change
MAX_SUBLINEAR_FRACTION = 0.75  # delta req/s growth <= 75% of world growth
MAX_RENDEZVOUS_S = 30.0
MAX_REGROW_S = 10.0


class _SimWorker(threading.Thread):
    """One fake rank: its own client, the real worker poll lifecycle."""

    daemon = True

    def __init__(self, wid: int, addr: str, key: bytes, mode: str,
                 poll_interval_s: float, long_poll_s: float,
                 stop: threading.Event):
        super().__init__(name=f"simworker-{wid}")
        self.wid = wid
        self.mode = mode
        self.poll_interval_s = poll_interval_s
        self.long_poll_s = long_poll_s
        self.stop = stop
        self.client = CoordinatorClient(addr, key,
                                        delta=(mode == "delta"))
        self.rendezvous_done: Optional[float] = None
        self.seen: Dict[int, float] = {}     # version -> first-seen ts

    def _poll(self) -> Optional[dict]:
        if self.mode == "legacy":
            return self.client.get_world()
        # Long-poll stretched to the advertised pacing: the worker is
        # parked (and instantly wakeable) nearly all the time, while its
        # request rate tracks the server's target instead of the interval.
        wait = self.long_poll_s
        adv = self.client.advertised_poll_s
        if adv and adv > wait:
            wait = adv
        return self.client.get_world(wait=wait)

    def _note(self, world: Optional[dict]) -> None:
        if world:
            v = world["version"]
            if v not in self.seen:
                self.seen[v] = time.perf_counter()

    def run(self) -> None:
        # Rendezvous: poll until the driver publishes the v1 world —
        # each arm the way its protocol ships it (legacy: interval-paced
        # full fetches; delta: parked long-poll, woken by the publish).
        while not self.stop.is_set():
            if self.mode == "legacy":
                world = self.client.get_world()
            else:
                world = self._poll()
            self._note(world)
            if world and world["version"] >= 1:
                self.rendezvous_done = time.perf_counter()
                break
            gap = self.poll_interval_s if self.mode == "legacy" else 0.02
            if self.stop.wait(gap):
                return
        # Steady state: the membership watch.
        while not self.stop.is_set():
            if self.mode == "legacy":
                if self.stop.wait(self.poll_interval_s):
                    return
                self._note(self.client.get_world())
            else:
                self._note(self._poll())
                if self.stop.wait(0.02):
                    return


def _register_all(addr: str, key: bytes, mode: str, hosts: Dict[str, int],
                  slots: int) -> None:
    """Registration as each protocol ships it: one thread per host
    process; per-worker posts (legacy) vs one batch post (delta)."""
    def one_host(i: int) -> None:
        c = CoordinatorClient(addr, key, delta=(mode == "delta"))
        pids = list(range(i * slots, (i + 1) * slots))
        if mode == "delta":
            c.register_batch(pids)
        else:
            for pid in pids:
                c.register(pid)
    threads = [threading.Thread(target=one_host, args=(i,), daemon=True)
               for i in range(len(hosts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)


def run_round(mode: str, n_workers: int, *, slots: int = 8,
              window_s: float = 6.0, changes: int = 2,
              poll_interval_s: float = C.DEFAULT_POLL_INTERVAL_S,
              long_poll_s: float = 1.0,
              journal_dir: Optional[str] = None) -> dict:
    """One fresh service + n_workers fake ranks under ``mode``; returns
    the round's metrics dict."""
    n_hosts = max(1, n_workers // slots)
    n_workers = n_hosts * slots
    key = _secret.make_secret_key()
    tmp_ctx = None
    if journal_dir is None:
        tmp_ctx = tempfile.TemporaryDirectory(prefix="hvd_cp_bench_")
        journal_dir = tmp_ctx.name
    journal_path = os.path.join(journal_dir, f"{mode}_{n_workers}.journal")
    svc = CoordinatorService(key, bind_host="127.0.0.1",
                             journal_path=journal_path)
    try:
        addr = f"127.0.0.1:{svc.port}"
        hosts = {f"host{i}": slots for i in range(n_hosts)}
        stop = threading.Event()
        workers = [_SimWorker(w, addr, key, mode, poll_interval_s,
                              long_poll_s, stop) for w in range(n_workers)]

        # --- rendezvous: register -> publish v1 -> everyone saw it ------
        t0 = time.perf_counter()
        _register_all(addr, key, mode, hosts, slots)
        deadline = t0 + 120
        while len(svc.registered_workers()) < n_workers \
                and time.perf_counter() < deadline:
            time.sleep(0.005)
        registered = len(svc.registered_workers())
        registration_s = time.perf_counter() - t0
        for w in workers:
            w.start()
        svc.update_world(hosts, n_workers)
        while any(w.rendezvous_done is None for w in workers) \
                and time.perf_counter() < deadline:
            time.sleep(0.005)
        rendezvous_s = max((w.rendezvous_done or time.perf_counter())
                           for w in workers) - t0
        journal_rendezvous_bytes = svc.journal_size_bytes()

        # --- quiet steady-state segment: NO publishes -------------------
        # Change wakeups are inherently linear in np (every worker must
        # hear every change); the *steady-state* request rate — what the
        # coordinator pays per second of calm — is measured with the
        # world held still. CPython int reads are atomic, so sampling the
        # workers' counters from here needs no locking.
        time.sleep(0.3)      # let per-worker pacing settle post-rendezvous
        quiet_s = max(1.0, window_s / 2)
        q_calls0 = sum(w.client.calls for w in workers)
        time.sleep(quiet_s)
        q_calls1 = sum(w.client.calls for w in workers)
        steady_reqs_per_s = (q_calls1 - q_calls0) / quiet_s

        # --- change window: interleaved membership changes --------------
        win0 = time.perf_counter()
        b_calls0 = sum(w.client.calls for w in workers)
        b_bytes0 = sum(w.client.bytes_received for w in workers)
        publish_at: Dict[int, float] = {}
        regrow_version = None
        for i in range(changes):
            time.sleep(window_s / (changes + 1))
            if i == 0:
                # Failure + shrunk world: the regrow cycle.
                svc.mark_failure("host0", 1)
                shrunk = {h: s for h, s in hosts.items() if h != "host0"}
                v = svc.update_world(shrunk or hosts,
                                     max(n_workers - slots, slots))
                regrow_version = v
            else:
                v = svc.update_world(hosts, n_workers)
            publish_at[v] = time.perf_counter()
        time.sleep(window_s / (changes + 1))
        window_elapsed = time.perf_counter() - win0
        calls = sum(w.client.calls for w in workers) - b_calls0
        bytes_ = sum(w.client.bytes_received for w in workers) - b_bytes0
        fallbacks = sum(w.client.snapshot_fallbacks for w in workers)
        resyncs = sum(w.client.resyncs for w in workers)

        # Let the stragglers observe the last publish before reading the
        # propagation latencies (still inside the round, not the window).
        last_v = max(publish_at)
        settle = time.perf_counter() + max(2 * poll_interval_s, 1.0)
        while any(last_v not in w.seen for w in workers) \
                and time.perf_counter() < settle:
            time.sleep(0.005)

        def propagation(v: Optional[int]) -> Optional[float]:
            if v is None or v not in publish_at:
                return None
            lats = [w.seen[v] - publish_at[v]
                    for w in workers if v in w.seen]
            return round(max(lats), 4) if lats else None

        regrow_s = propagation(regrow_version)
        regrow_coverage = (sum(1 for w in workers
                               if regrow_version in w.seen) / n_workers
                           if regrow_version is not None else 0.0)

        # --- teardown: wake every parked long-poll, then stop ------------
        stop.set()
        svc.update_world(hosts, n_workers)   # release publish (unmeasured)
        for w in workers:
            w.join(timeout=10)
        return {
            "mode": mode, "n_workers": n_workers, "n_hosts": n_hosts,
            "registered": registered,
            "registration_s": round(registration_s, 4),
            "rendezvous_s": round(rendezvous_s, 4),
            "regrow_s": regrow_s,
            "regrow_coverage": round(regrow_coverage, 4),
            "window_s": round(window_elapsed, 4),
            "quiet_s": round(quiet_s, 4),
            "changes": changes,
            "reqs_per_s": round(steady_reqs_per_s, 2),
            "change_reqs_per_s": round(calls / window_elapsed, 2),
            "bytes_per_change": round(bytes_ / max(changes, 1), 1),
            "window_bytes": bytes_,
            "window_calls": calls,
            "snapshot_fallbacks": fallbacks,
            "resyncs": resyncs,
            "journal_rendezvous_bytes": journal_rendezvous_bytes,
        }
    finally:
        svc.close()
        if tmp_ctx is not None:
            tmp_ctx.cleanup()


# -- journal compaction equivalence -----------------------------------------


def _mutation_stream(svc: CoordinatorService, n_hosts: int = 8,
                     slots: int = 8) -> None:
    """A deterministic churny history: registrations, world updates,
    failures — far more records than the compaction cadence used below."""
    hosts = {f"host{i}": slots for i in range(n_hosts)}
    svc._record_register_batch(list(range(n_hosts * slots)), ts=0.0)
    for gen in range(40):
        dead = f"host{gen % n_hosts}"
        svc.mark_failure(dead, code=1 + gen % 3)
        if gen % 3 == 2:
            svc.mark_failure(f"host{(gen + 1) % n_hosts}", code=9)
        live = {h: s for h, s in hosts.items() if h != dead}
        svc.update_world(live, (n_hosts - 1) * slots)
        svc.update_world(hosts, n_hosts * slots)
        svc._record_register(1000 + gen, ts=float(gen))


def journal_compaction_check(workdir: str) -> dict:
    """Same mutation stream with compaction off vs on (cadence 16),
    crash the compacted service, replay both journals: every field of
    the rebuilt state — ``version`` and ``failure_seq`` above all — must
    match (registration timestamps compared by key: wall ts differs)."""
    key = _secret.make_secret_key()
    results = {}
    states = {}
    for label, cadence in (("uncompacted", "0"), ("compacted", "16")):
        path = os.path.join(workdir, f"{label}.journal")
        old = os.environ.get(C.COMPACT_EVERY_ENV)
        os.environ[C.COMPACT_EVERY_ENV] = cadence
        try:
            svc = CoordinatorService(key, bind_host="127.0.0.1",
                                     journal_path=path)
        finally:
            if old is None:
                os.environ.pop(C.COMPACT_EVERY_ENV, None)
            else:
                os.environ[C.COMPACT_EVERY_ENV] = old
        _mutation_stream(svc)
        live = (svc.version, svc.failure_seq)
        results[f"{label}_bytes"] = svc.journal_size_bytes()
        if label == "compacted":
            svc.simulate_crash()     # rebuild must survive a dirty death
        else:
            svc.close()
        state = journal_mod.replay(path)
        assert state is not None, f"{label} journal replayed empty"
        assert (state["version"], state["failure_seq"]) == live, \
            f"{label}: replay {state['version']}/{state['failure_seq']} " \
            f"!= live {live}"
        states[label] = state
    u, c = states["uncompacted"], states["compacted"]
    results["rebuild_counters_match"] = (
        u["version"] == c["version"]
        and u["failure_seq"] == c["failure_seq"]
        and u["hosts"] == c["hosts"] and u["np"] == c["np"]
        and u["failures"] == c["failures"]
        and sorted(u["registrations"]) == sorted(c["registrations"]))
    results["compaction_ratio"] = round(
        results["uncompacted_bytes"] / max(results["compacted_bytes"], 1), 2)
    return results


# -- aggregation -------------------------------------------------------------


def _median(vals: List[float]) -> Optional[float]:
    vals = [v for v in vals if v is not None]
    return round(statistics.median(vals), 4) if vals else None


def _noise(ratios: List[float]) -> dict:
    """The noise band STATED with the measurement (scaling.py
    convention): round count + min/max/spread of the per-round ratios."""
    rs = sorted(ratios)
    return {"rounds": len(rs),
            "ratio_min": round(rs[0], 4),
            "ratio_max": round(rs[-1], 4),
            "spread": round(rs[-1] - rs[0], 4)}


def run_harness(sizes: List[int], rounds: int, *, slots: int,
                window_s: float, changes: int, poll_interval_s: float,
                long_poll_s: float) -> dict:
    arms: Dict[str, Dict[str, list]] = {}
    pair_ratios: Dict[str, List[float]] = {}
    with tempfile.TemporaryDirectory(prefix="hvd_cp_bench_") as workdir:
        for size in sizes:
            arms[str(size)] = {"legacy": [], "delta": []}
            pair_ratios[str(size)] = []
            for r in range(rounds):
                # Interleaved: legacy then delta inside every round-pair,
                # so drift (CPU load, page cache) hits both arms alike.
                leg = run_round("legacy", size, slots=slots,
                                window_s=window_s, changes=changes,
                                poll_interval_s=poll_interval_s,
                                long_poll_s=long_poll_s,
                                journal_dir=workdir)
                dlt = run_round("delta", size, slots=slots,
                                window_s=window_s, changes=changes,
                                poll_interval_s=poll_interval_s,
                                long_poll_s=long_poll_s,
                                journal_dir=workdir)
                arms[str(size)]["legacy"].append(leg)
                arms[str(size)]["delta"].append(dlt)
                pair_ratios[str(size)].append(
                    leg["bytes_per_change"] / max(dlt["bytes_per_change"],
                                                  1.0))
        compaction = journal_compaction_check(workdir)

    def med(size: int, mode: str, field: str) -> Optional[float]:
        return _median([r[field] for r in arms[str(size)][mode]])

    lo, hi = min(sizes), max(sizes)
    reqs = {m: {str(s): med(s, m, "reqs_per_s") for s in sizes}
            for m in ("legacy", "delta")}
    growth = {m: round(reqs[m][str(hi)] / max(reqs[m][str(lo)], 0.01), 3)
              for m in ("legacy", "delta")}
    rec = {
        "bench": "control_plane",
        "sizes": sizes, "slots": slots, "rounds": rounds,
        "window_s": window_s, "changes": changes,
        "poll_interval_s": poll_interval_s, "long_poll_s": long_poll_s,
        "bytes_per_change": {
            m: {str(s): med(s, m, "bytes_per_change") for s in sizes}
            for m in ("legacy", "delta")},
        # Headline: legacy/delta response bytes per membership change at
        # the LARGEST size, median over interleaved round pairs.
        "bytes_per_change_ratio": {
            str(s): _median(pair_ratios[str(s)]) for s in sizes},
        "noise": _noise(pair_ratios[str(hi)]),
        "reqs_per_s": reqs,
        "change_reqs_per_s": {
            m: {str(s): med(s, m, "change_reqs_per_s") for s in sizes}
            for m in ("legacy", "delta")},
        # Sub-linearity: QUIET-segment req/s growth lo->hi vs the
        # world-size growth (change wakeups are linear by necessity).
        "reqs_growth": {**growth, "world_growth": round(hi / lo, 3)},
        "rendezvous_s": {
            m: {str(s): med(s, m, "rendezvous_s") for s in sizes}
            for m in ("legacy", "delta")},
        "registration_s": {
            m: {str(s): med(s, m, "registration_s") for s in sizes}
            for m in ("legacy", "delta")},
        "regrow_s": {
            m: {str(s): med(s, m, "regrow_s") for s in sizes}
            for m in ("legacy", "delta")},
        "journal_rendezvous_bytes": {
            m: {str(s): med(s, m, "journal_rendezvous_bytes")
                for s in sizes}
            for m in ("legacy", "delta")},
        "snapshot_fallbacks": sum(
            r["snapshot_fallbacks"]
            for by in arms.values() for rs in by.values() for r in rs),
        "resyncs": sum(
            r["resyncs"]
            for by in arms.values() for rs in by.values() for r in rs),
        "journal_compaction": compaction,
    }
    return rec


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


# -- --check: guardrail over the recorded series -----------------------------


def check_history(path: str = HISTORY_PATH) -> dict:
    """Validate the NEWEST history record: the keys the guardrail test
    pins must exist and sit inside the rails. Returns the verdict dict
    (ok + per-criterion detail); raises on a missing/empty series."""
    with open(path, "r", encoding="utf-8") as fh:
        recs = [json.loads(line) for line in fh if line.strip()]
    recs = [r for r in recs if r.get("bench") == "control_plane"]
    if not recs:
        raise ValueError(f"no control_plane records in {path}")
    rec = recs[-1]
    sizes = rec["sizes"]
    hi = str(max(sizes))
    problems = []

    def need(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)

    need(max(sizes) >= 256, f"largest size {hi} < 256 workers")
    ratio = (rec.get("bytes_per_change_ratio") or {}).get(hi)
    need(isinstance(ratio, (int, float)) and ratio >= MIN_BYTES_RATIO,
         f"bytes_per_change_ratio[{hi}]={ratio} < {MIN_BYTES_RATIO}x")
    noise = rec.get("noise") or {}
    need(noise.get("rounds", 0) >= 2
         and all(k in noise for k in ("ratio_min", "ratio_max", "spread")),
         f"noise band incomplete: {noise}")
    growth = rec.get("reqs_growth") or {}
    world = growth.get("world_growth") or (max(sizes) / min(sizes))
    need(isinstance(growth.get("delta"), (int, float))
         and growth["delta"] <= MAX_SUBLINEAR_FRACTION * world,
         f"delta req/s growth {growth.get('delta')} not sub-linear "
         f"(world growth {world})")
    for mode in ("legacy", "delta"):
        rdv = (rec.get("rendezvous_s") or {}).get(mode, {}).get(hi)
        need(isinstance(rdv, (int, float)) and 0 < rdv < MAX_RENDEZVOUS_S,
             f"rendezvous_s[{mode}][{hi}]={rdv} outside (0, "
             f"{MAX_RENDEZVOUS_S})")
    regrow = (rec.get("regrow_s") or {}).get("delta", {}).get(hi)
    need(isinstance(regrow, (int, float)) and 0 < regrow < MAX_REGROW_S,
         f"regrow_s[delta][{hi}]={regrow} outside (0, {MAX_REGROW_S})")
    comp = rec.get("journal_compaction") or {}
    need(comp.get("rebuild_counters_match") is True,
         "journal compaction rebuild does not match uncompacted replay")
    need(comp.get("compaction_ratio", 0) > 1.0,
         f"compaction did not shrink the journal: {comp}")
    return {"check": "control_plane", "ok": not problems,
            "record_date": rec.get("date"), "record_git": rec.get("git"),
            "problems": problems}


# -- entry points ------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default="64,256",
                    help="comma-separated simulated world sizes")
    ap.add_argument("--rounds", type=int, default=3,
                    help="interleaved legacy/delta round pairs per size")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--window", type=float, default=6.0,
                    help="membership-change window per round, s (one "
                         "change every window/(changes+1) s — already "
                         "far churnier than any real fleet)")
    ap.add_argument("--changes", type=int, default=2,
                    help="membership changes inside each window")
    ap.add_argument("--poll-interval", type=float,
                    default=C.DEFAULT_POLL_INTERVAL_S)
    ap.add_argument("--long-poll", type=float, default=1.0)
    ap.add_argument("--check", action="store_true",
                    help="validate the newest history record and exit")
    ap.add_argument("--smoke", type=int, default=0, metavar="N",
                    help="one delta round at N workers (chaos-tier "
                         "budget test); prints that round's JSON")
    a = ap.parse_args(argv)

    if a.check:
        verdict = check_history()
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1

    if a.smoke:
        res = run_round("delta", a.smoke, slots=a.slots,
                        window_s=min(a.window, 1.5), changes=1,
                        poll_interval_s=a.poll_interval,
                        long_poll_s=a.long_poll)
        print(json.dumps({"bench": "control_plane_smoke", **res}))
        ok = (res["registered"] == res["n_workers"]
              and res["regrow_s"] is not None
              and res["regrow_coverage"] == 1.0)
        return 0 if ok else 1

    sizes = sorted({int(s) for s in a.sizes.split(",") if s.strip()})
    rec = run_harness(sizes, a.rounds, slots=a.slots, window_s=a.window,
                      changes=a.changes, poll_interval_s=a.poll_interval,
                      long_poll_s=a.long_poll)
    print(json.dumps(rec))
    if os.environ.get(NO_HISTORY_ENV, "").lower() not in ("1", "true"):
        _append_history(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
