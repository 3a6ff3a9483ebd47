"""Pod-scale control-plane guardrails over benchmarks/control_plane.py.

The COMMITTED history record (benchmarks/control_plane_history.jsonl) must
stay inside the rails — ≥5× fewer response bytes per membership change
at ≥256 workers, sub-linear steady-state request growth, and a journal
compaction rebuild that matches the uncompacted replay — so a regression
in the delta protocol, the long-poll path, or compaction fails tier-1
without re-running the (multi-minute) harness. The harness itself runs
in the chaos tier via the slow-marked ≥200-worker smoke below.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "control_plane.py")
HISTORY = os.path.join(REPO, "benchmarks", "control_plane_history.jsonl")


def _run(args, timeout):
    env = dict(os.environ, HOROVOD_CONTROL_PLANE_NO_HISTORY="1")
    env.pop("HOROVOD_FAULT_SPEC", None)
    return subprocess.run([sys.executable, BENCH, *args],
                          capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)


def test_history_record_is_complete():
    """The committed record carries everything --check pins, with the
    noise band STATED (CLAUDE.md: a ratio without its spread is noise)."""
    with open(HISTORY, encoding="utf-8") as fh:
        recs = [json.loads(line) for line in fh if line.strip()]
    recs = [r for r in recs if r.get("bench") == "control_plane"]
    assert recs, "no control_plane records committed"
    rec = recs[-1]
    assert max(rec["sizes"]) >= 256
    assert rec["noise"]["rounds"] >= 2
    for k in ("ratio_min", "ratio_max", "spread"):
        assert k in rec["noise"]
    for k in ("bytes_per_change_ratio", "reqs_per_s", "reqs_growth",
              "rendezvous_s", "regrow_s", "journal_compaction"):
        assert k in rec, f"history record missing {k}"
    assert rec.get("date") and rec.get("git")


def test_recorded_series_inside_rails():
    """Fast tier-1 guardrail: run the harness's own --check validator
    against the committed series."""
    p = _run(["--check"], timeout=60)
    out = (p.stdout.strip().splitlines() or ["{}"])[-1]
    verdict = json.loads(out)
    assert p.returncode == 0 and verdict.get("ok"), (verdict, p.stderr)


@pytest.mark.slow
def test_scale_smoke_200_workers_in_budget():
    """Chaos tier: ≥200 simulated workers rendezvous against one real
    coordinator, then survive one failure + regrow publish — all inside
    a fixed budget (subprocess timeout is the budget)."""
    p = _run(["--smoke", "200"], timeout=180)
    assert p.returncode == 0, (p.stdout, p.stderr)
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["registered"] == res["n_workers"] >= 200
    assert 0 < res["rendezvous_s"] < 60
    assert res["regrow_s"] is not None and res["regrow_s"] < 10
    assert res["regrow_coverage"] == 1.0
    assert res["resyncs"] == 0
