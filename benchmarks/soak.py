"""Chaos-soak guardrail (ISSUE 20): one seeded schedule drawn from the
WHOLE fault menu (testing/faults.py — kill / hang / delay / corrupt /
nan / desync / torn / preempt / rpc_* / resume_* / replica_* /
traffic_spike) thrown at a live np=3 train + publish + serve world, then
judged on global invariants (horovod_tpu/testing/soak.py): training
completes every step exactly once with bounded rollback, zero
accepted-request loss on the serving side, coordinator-journal replay
reproduces both final worlds, crash-class faults leave flight dumps
while graceful preemptions leave none, the last commit restores in a
fresh process, and no orphaned processes survive.

The schedule is a pure function of ``--seed`` (same seed, same
schedule — a red soak is re-runnable byte for byte; pinned by
tests/test_soak.py). Emits one JSON line and
appends it — stamped with date + git SHA — to
``benchmarks/soak_history.jsonl`` unless ``HOROVOD_SOAK_NO_HISTORY`` is
set. ``--check`` validates the newest committed record against the
rails; ``--smoke`` runs the shrunk fixed-seed tier-1 profile (benign-
heavy, one preemption, no history).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks import common  # noqa: E402,F401  (forces cpu backend)
from horovod_tpu.testing.soak import run_soak  # noqa: E402

HISTORY_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "soak_history.jsonl")
NO_HISTORY_ENV = "HOROVOD_SOAK_NO_HISTORY"

#: Default seed for the committed record. Any seed must pass — the rails
#: below are seed-independent — but the committed history stays on one
#: seed so regressions diff against an identical schedule.
DEFAULT_SEED = 20

#: --check rails (ISSUE 20 acceptance): the run survived at least this
#: many distinct chaos events with EVERY invariant green.
MIN_EVENTS_FIRED = 20


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
    """Validate the NEWEST committed record: every invariant green,
    enough events actually fired (a soak that silently skipped its chaos
    proves nothing), zero accepted-request loss, and a crash-free
    graceful-preemption trail unless a crash fault was scheduled."""
    with open(path, "r", encoding="utf-8") as fh:
        recs = [json.loads(line) for line in fh if line.strip()]
    recs = [r for r in recs if r.get("bench") == "soak"]
    if not recs:
        raise ValueError(f"no soak records in {path}")
    rec = recs[-1]
    problems: List[str] = []

    def need(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)

    need(rec.get("ok") is True,
         f"record not ok: problems={rec.get('problems')}")
    invs = rec.get("invariants") or {}
    need(bool(invs) and all(invs.values()),
         f"invariant(s) red: "
         f"{sorted(k for k, v in invs.items() if not v)}")
    need(rec.get("events_fired", 0) >= MIN_EVENTS_FIRED,
         f"events_fired={rec.get('events_fired')} < {MIN_EVENTS_FIRED}")
    by_kind = rec.get("fired_by_kind") or {}
    need(by_kind.get("preempt", 0) >= 2,
         f"preemption path under-exercised: {by_kind}")
    need(len(by_kind) >= 8,
         f"fault-kind diversity too low ({len(by_kind)} kinds): {by_kind}")
    reqs = rec.get("requests") or {}
    need(reqs.get("failed") == 0 and reqs.get("served", 0) > 0,
         f"accepted-request loss (or no traffic): {reqs}")
    need(len(rec.get("generations") or []) >= 4,
         f"world never churned: generations={rec.get('generations')}")
    need(rec.get("publishes", 0) >= 3,
         f"publish plane under-exercised: {rec.get('publishes')}")
    return {"check": "soak", "ok": not problems,
            "record_date": rec.get("date"), "record_git": rec.get("git"),
            "problems": problems}


# -- entry points -------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED,
                    help="schedule seed (same seed => same schedule)")
    ap.add_argument("--steps", type=int, default=None,
                    help="override the profile's training step count")
    ap.add_argument("--events", type=int, default=None,
                    help="override the profile's scheduled event count")
    ap.add_argument("--check", action="store_true",
                    help="validate the newest history record and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="fixed-seed shrunk profile, no history (tier-1)")
    a = ap.parse_args(argv)

    if a.check:
        verdict = check_history()
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1

    profile = "smoke" if a.smoke else "full"
    # HOROVOD_SOAK_WORKDIR keeps the run's artifacts (journals, ledger,
    # train.log, flight dumps) for post-mortem instead of a tempdir.
    keep = os.environ.get("HOROVOD_SOAK_WORKDIR")
    if keep:
        os.makedirs(keep, exist_ok=True)
        rec = run_soak(a.seed, keep, profile=profile,
                       steps=a.steps, events=a.events)
    else:
        with tempfile.TemporaryDirectory(prefix="hvd_soak_") as workdir:
            rec = run_soak(a.seed, workdir, profile=profile,
                           steps=a.steps, events=a.events)
    print(json.dumps(rec))
    if not a.smoke and os.environ.get(
            NO_HISTORY_ENV, "").lower() not in ("1", "true"):
        _append_history(rec)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
