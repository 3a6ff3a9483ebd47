"""Step-time attribution, per-model MFU ratchet, and perf regression diffs.

Reference analog: the per-op ``horovod/common/timeline.cc`` record (where a
step's time goes, op by op) paired with the autotuner's measurement loop
(``horovod/common/autotuner`` — measure, persist, only accept configs that
beat the incumbent). Here the measurement source is an ``xplane`` trace
(``jax.profiler``) and the persistence is a JSON-lines history
(``$HOROVOD_PERF_HISTORY``, by default ``benchmarks/perf_history.jsonl``,
which is written at run time and not committed): each model's best recorded
MFU becomes a railed floor (``tools.perf check``). What decides a PR's speed
is ``python3 -m chipbench.run`` and ``PERF_LEDGER.jsonl``, not this history.

The canonical artifact is the **step-time budget**: device time decomposed
into disjoint occupancy categories that sum to device wall within tolerance:

- ``matmul/conv``       dots, einsums, convolutions (the MFU numerator path)
- ``gather/scatter``    embedding/dispatch indexing (TPU scatters serialize!)
- ``copy/transpose``    layout copies — the r4 DLRM killer (CLAUDE.md: XLA's
                        entry-layout heuristic can transpose WHOLE tensors)
- ``elementwise``       fusions, reductions, batch-norm, the long tail
- ``collective_exposed``/``collective_hidden``  on-lane collective occupancy,
                        split by its intersection with concurrent compute
- ``other``             uncategorized leaf ops
- ``host_gap``          wall minus leaf occupancy: infeed/dispatch bubbles

Two xplane traps are load-bearing (CLAUDE.md, and ``lint-xplane-umbrella``
enforces them repo-wide): ``%while``/``tuple.``/``jit_`` events are scan/
module *umbrellas* whose spans cover their children — counting them double
counts the step; "Async XLA Ops" are overlapped DMA *windows*, not occupancy
— they only feed the hidden-collective intersection.

Module-level imports are stdlib-only on purpose: the CLI reads a history
without jax or tensorflow; ``load_xspace`` and ``attribute_logdir`` import
them when a trace is parsed.

CLI::

    python -m horovod_tpu.tools.perf show  [--history P] [--model M]
    python -m horovod_tpu.tools.perf diff A B [--history P] [--json]
    python -m horovod_tpu.tools.perf check [--history P] [--band X] [--json]

See docs/profiling.md for the budget categories and the ratchet workflow.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import math
import os
import re
from typing import Any, Dict, List, Optional, Tuple

# --------------------------------------------------------------- env knobs

#: Override the history file path (tests point this at a tmp file).
HISTORY_ENV = "HOROVOD_PERF_HISTORY"
#: Truthy: profile runs do not append to the committed history (CI).
NO_HISTORY_ENV = "HOROVOD_PERF_NO_HISTORY"
#: Ratchet band: the latest MFU may sit this fraction below the model's
#: best before ``check`` fails (the run-to-run spread on the chip has
#: not been measured since the band was set; PERF.md "Open questions").
RATCHET_BAND_ENV = "HOROVOD_PERF_RATCHET_BAND"
DEFAULT_RATCHET_BAND = 0.90
#: Shape rail: budget categories must sum to device wall within this.
SUM_TOLERANCE = 0.05
#: Absolute per-arm floors for ``kind: "spec_decode"`` records
#: (benchmarks/serving.py spec segment, ISSUE 16): the repeat-heavy arm
#: must keep the n-gram drafter paying off, and the adversarial
#: all-rejected arm must stay a near-free fallback — the lossless rail.
SPEC_DECODE_FLOORS = {"repeat_heavy": 1.5, "adversarial": 0.9}

# ------------------------------------------------------- xplane trap lore

#: Scan-loop / tuple / jitted-module umbrella event prefixes: spans that
#: COVER their leaf children — never occupancy (CLAUDE.md trap).
UMBRELLA_PREFIXES = ("while", "tuple.", "jit_")

#: CPU thunk events are bare HLO op names ("dot.3", "all-reduce.1");
#: anything with spaces/colons is client infra (ExecuteHelper, listeners).
CPU_OP_RE = re.compile(r"^%?[A-Za-z][\w.\-]*$")

COLLECTIVE_RE = re.compile(
    r"all-reduce|all_reduce|reduce-scatter|reduce_scatter|all-gather|"
    r"all_gather|all-to-all|all_to_all|collective-permute|collective")

#: Ordered, first-match-wins budget categories over the SHORT op name
#: (lower-cased). gather/scatter precedes copy/transpose so dynamic-slice
#: lands with the indexing traffic.
BUDGET_CATEGORIES: Tuple[Tuple[str, Any], ...] = (
    ("collective", COLLECTIVE_RE),
    ("gather/scatter", re.compile(r"gather|scatter|dynamic-slice|"
                                  r"dynamic-update")),
    ("matmul/conv", re.compile(r"^dot|einsum|matmul|convolution|conv\d|"
                               r"^conv")),
    ("copy/transpose", re.compile(r"copy|transpose|bitcast|slice")),
    ("elementwise", re.compile(r"fusion|fused|reduce|batch-norm|sort|"
                               r"add|sub|mul|div|select|compare|convert|"
                               r"broadcast|iota|exp|log|tanh|max|min|rsqrt")),
)

#: Budget keys every record must carry (the ``check`` shape rail).
BUDGET_KEYS = ("matmul/conv", "gather/scatter", "copy/transpose",
               "elementwise", "collective_exposed", "collective_hidden",
               "other", "host_gap")


def short_name(name: str) -> str:
    """'%loop_fusion.12 = bf16[...] fusion(...)' -> 'loop_fusion.12'"""
    return name.split(" = ")[0].lstrip("%")


def categorize_budget(name: str) -> str:
    """Budget category of one HLO instruction (full text or short name)."""
    low = short_name(name).lower()
    for cat, pat in BUDGET_CATEGORIES:
        if pat.search(low):
            return cat
    return "other"


# ------------------------------------------------------- interval algebra

def merge_intervals(intervals: List) -> List:
    """Sorted union of (start, end) intervals."""
    intervals.sort()
    merged: List = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def intersect_ps(spans: List, union: List) -> int:
    """Σ over ``spans`` of their intersection with the merged ``union``."""
    starts = [m[0] for m in union]
    hidden = 0
    for s, e in spans:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(union) and union[i][0] < e:
            hidden += max(0, min(e, union[i][1]) - max(s, union[i][0]))
            i += 1
    return hidden


# ----------------------------------------------------- budget computation

def load_xspace(logdir: str):
    """Parsed XSpace proto of the newest xplane.pb under ``logdir``."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {logdir}")
    space = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        space.ParseFromString(f.read())
    return space


def budget_from_space(space) -> Dict[str, Any]:
    """Per-lane step-time budget over every device lane in ``space``.

    A *lane* is a serial execution line: the "XLA Ops" line of each TPU
    core plane, or each executor-thread line of the ``/host:CPU`` plane
    (thunk runtime). Per lane, leaf-op occupancy is categorized and the
    gap (lane wall − leaf occupancy) absorbs infeed/dispatch bubbles, so
    categories + gap = wall *by construction* — the sum-to-wall property
    the tests rail. TPU planes prefer the "XLA Modules" total as wall
    (covers intra-module bubbles the op line hides); "Async XLA Ops"
    windows feed only the hidden-collective intersection.

    Returns picoseconds: ``{"wall_ps", "cat_ps": {category: ps},
    "op_ps": {category: {op: ps}}, "op_n": {op: count},
    "hidden_ps", "collective_total_ps", "n_lanes"}``.
    """
    cat_ps: collections.Counter = collections.Counter()
    op_ps: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    op_n: collections.Counter = collections.Counter()
    wall_ps = 0
    hidden_ps = 0
    coll_total_ps = 0
    n_lanes = 0
    for plane in space.planes:
        is_tpu = "/device:TPU" in plane.name
        is_cpu = plane.name == "/host:CPU"
        if not (is_tpu or is_cpu):
            continue
        meta = plane.event_metadata
        modules_ps = 0
        lanes_extent_ps = 0
        plane_coll: List = []
        plane_comp: List = []
        plane_occupancy = 0
        for line in plane.lines:
            if is_tpu and line.name == "XLA Modules":
                # Module wall (per-core serial), not occupancy — the
                # vetted wall source; umbrella filtering is moot here.
                modules_ps += sum(  # hvd-analyze: ok — wall, not occupancy
                    ev.duration_ps for ev in line.events)
                continue
            if is_tpu and line.name == "Async XLA Ops":
                # Overlapped DMA windows, NOT occupancy (CLAUDE.md trap):
                # they exist only for async collectives and feed the
                # hidden-time intersection below, nothing else.
                for ev in line.events:  # hvd-analyze: ok — overlap spans
                    if ev.duration_ps > 0:
                        plane_coll.append(
                            (ev.offset_ps, ev.offset_ps + ev.duration_ps))
            if is_tpu and line.name != "XLA Ops":
                continue
            if is_cpu and line.name == "python":
                continue
            lo = hi = None
            for ev in line.events:
                if ev.duration_ps <= 0:
                    continue
                name = meta[ev.metadata_id].name \
                    if ev.metadata_id in meta else ""
                stripped = name.lstrip("%")
                if stripped.startswith(UMBRELLA_PREFIXES):
                    continue  # scan/module umbrellas, not leaf work
                if is_cpu and not CPU_OP_RE.match(name):
                    continue  # client-infra span, not an HLO op
                start = ev.offset_ps
                end = start + ev.duration_ps
                lo = start if lo is None else min(lo, start)
                hi = end if hi is None else max(hi, end)
                cat = categorize_budget(name)
                cat_ps[cat] += ev.duration_ps
                plane_occupancy += ev.duration_ps
                sn = short_name(name)
                op_ps[cat][sn] += ev.duration_ps
                op_n[sn] += 1
                if cat == "collective":
                    plane_coll.append((start, end))
                    coll_total_ps += ev.duration_ps
                else:
                    plane_comp.append((start, end))
            if lo is not None:
                lanes_extent_ps += hi - lo
                n_lanes += 1
        if is_tpu and modules_ps:
            wall_ps += modules_ps
        else:
            wall_ps += lanes_extent_ps
        hidden_ps += intersect_ps(plane_coll, merge_intervals(plane_comp))
    # Budget partition: split on-lane collective occupancy into exposed vs
    # hidden (hidden = overlapped by concurrent compute on other lanes /
    # async windows — clamped so the partition stays exact; async-only
    # hidden time beyond lane occupancy is visible via overlap_fraction).
    coll_occ = cat_ps.pop("collective", 0)
    hidden_occ = min(hidden_ps, coll_occ)
    cat_ps["collective_hidden"] = hidden_occ
    cat_ps["collective_exposed"] = coll_occ - hidden_occ
    if "collective" in op_ps:
        # one op table for both halves — the split is temporal, not per-op
        op_ps["collective_exposed"] = op_ps.pop("collective")
    cat_ps["host_gap"] = wall_ps - coll_occ - sum(
        v for k, v in cat_ps.items()
        if k not in ("host_gap", "collective_hidden", "collective_exposed"))
    for key in BUDGET_KEYS:
        cat_ps.setdefault(key, 0)
    return {"wall_ps": wall_ps, "cat_ps": dict(cat_ps),
            "op_ps": {c: dict(t) for c, t in op_ps.items()},
            "op_n": dict(op_n), "hidden_ps": hidden_ps,
            "collective_total_ps": coll_total_ps, "n_lanes": n_lanes}


def attribute_logdir(logdir: str, steps: int, *, model: str,
                     metric: Optional[str] = None,
                     flops_per_step: Optional[float] = None,
                     extra: Optional[Dict[str, Any]] = None,
                     top_k: int = 3) -> Dict[str, Any]:
    """One attribution record for the newest trace under ``logdir``.

    ``steps`` is the number of train steps the trace covered; all
    per-step figures divide by it. The record is the perf_history.jsonl
    schema: per-category seconds, sum-to-wall check, top offending ops
    per category, and MFU when ``flops_per_step`` and the device peak are
    both known (``achieved_tflops`` otherwise, so CPU-mesh records still
    carry a throughput figure for ``diff``).
    """
    steps = max(int(steps), 1)
    b = budget_from_space(load_xspace(logdir))
    wall_s = b["wall_ps"] / 1e12
    cat_sum_ps = sum(b["cat_ps"].values())
    budget_s = {c: round(ps / 1e12 / steps, 6)
                for c, ps in sorted(b["cat_ps"].items())}
    top_ops: Dict[str, List[Dict[str, Any]]] = {}
    for cat, table in b["op_ps"].items():
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:top_k]
        top_ops[cat] = [
            {"op": op, "ms_per_step": round(ps / 1e9 / steps, 3),
             "share": round(ps / max(b["wall_ps"], 1), 4),
             "n": b["op_n"].get(op, 0)}
            for op, ps in ranked]
    rec: Dict[str, Any] = {
        "kind": "perf_budget",
        "metric": metric or f"{model}_step_budget",
        "model": model,
        "steps": steps,
        "n_lanes": b["n_lanes"],
        "wall_s_per_step": round(wall_s / steps, 6),
        "budget_s_per_step": budget_s,
        "sum_check": {
            "sum_s": round(cat_sum_ps / 1e12 / steps, 6),
            "wall_s": round(wall_s / steps, 6),
            "rel_err": round(abs(cat_sum_ps - b["wall_ps"])
                             / max(b["wall_ps"], 1), 6),
        },
        "top_ops": top_ops,
        "overlap": {
            "collective_ms": round(b["collective_total_ps"] / 1e9, 3),
            "hidden_ms": round(b["hidden_ps"] / 1e9, 3),
        },
    }
    try:  # device identity: best-effort (jax may be absent in CLI use)
        import jax
        dev = jax.devices()[0]
        rec["device"] = getattr(dev, "device_kind", dev.platform)
        rec["n_devices"] = jax.device_count()
    except Exception:
        pass
    if flops_per_step and math.isfinite(flops_per_step) and wall_s > 0:
        rec["flops_per_step"] = float(flops_per_step)
        achieved = flops_per_step / (wall_s / steps)
        rec["achieved_tflops"] = round(achieved / 1e12, 4)
        peak = device_peak_flops()
        if math.isfinite(peak):
            rec["mfu"] = round(achieved / peak, 4)
            rec["peak_tflops"] = round(peak / 1e12, 1)
    if extra:
        rec.update({k: v for k, v in extra.items() if k not in rec})
    return rec


def print_budget(rec: Dict[str, Any]) -> None:
    """Human-readable budget table for one record + its JSON line."""
    wall = rec["wall_s_per_step"]
    print(f"\nstep budget [{rec['model']}]: "
          f"{wall * 1e3:.2f} ms/step wall over {rec['steps']} steps "
          f"({rec['n_lanes']} lanes); sum/wall rel_err "
          f"{rec['sum_check']['rel_err']:.3f}")
    for cat, sec in sorted(rec["budget_s_per_step"].items(),
                           key=lambda kv: -kv[1]):
        share = sec / wall if wall else 0.0
        tops = rec["top_ops"].get(cat, [])
        lead = f" — top: {tops[0]['op']}" if tops else ""
        print(f"  {cat:<20} {sec * 1e3:>9.3f} ms {share:>6.1%}{lead}")
    if "mfu" in rec:
        print(f"  MFU {100 * rec['mfu']:.1f}% "
              f"({rec['achieved_tflops']:.2f} of "
              f"{rec['peak_tflops']:.0f} peak TFLOP/s)")
    elif "achieved_tflops" in rec:
        print(f"  achieved {rec['achieved_tflops']:.3f} TFLOP/s "
              "(device peak unknown — no MFU)")
    print(json.dumps(rec))


# ------------------------------------------------------------ device peak

_PEAK_TABLE = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def device_peak_flops(device=None) -> float:
    """Per-chip bf16 peak FLOP/s by device kind (public TPU spec sheet);
    NaN when unknown (CPU, unrecognized kinds) — callers omit MFU then."""
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:
            return float("nan")
    return _peak_for_kind(getattr(device, "device_kind", ""))


def _peak_for_kind(kind: str) -> float:
    kind = (kind or "").lower()
    for key, val in _PEAK_TABLE:
        if key in kind:
            return val
    return float("nan")


# --------------------------------------------------------------- history

def history_path(path: Optional[str] = None) -> str:
    if path:
        return path
    env = os.environ.get(HISTORY_ENV)
    if env:
        return env
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo, "benchmarks", "perf_history.jsonl")


def append_history(record: Dict[str, Any],
                   path: Optional[str] = None) -> Optional[str]:
    """Append one record (stamped with UTC date + git sha) to the perf
    history; returns the path, or
    None when ``HOROVOD_PERF_NO_HISTORY`` suppressed the append (CI)."""
    if os.environ.get(NO_HISTORY_ENV, "").lower() in ("1", "true"):
        return None
    import datetime
    import subprocess
    target = history_path(path)
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=os.path.dirname(os.path.abspath(target))
        ).stdout.strip() or None
    except OSError:
        sha = None
    stamp = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    with open(target, "a") as f:
        f.write(json.dumps({"date": stamp, "git": sha, **record}) + "\n")
    return target


def load_history(path: Optional[str] = None) -> List[Dict[str, Any]]:
    target = history_path(path)
    if not os.path.exists(target):
        return []
    out = []
    with open(target) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


# --------------------------------------------------------------- ratchet

def ratchet_check(history: List[Dict[str, Any]],
                  band: Optional[float] = None) -> Tuple[bool, List[str]]:
    """The MFU ratchet + shape rail over a loaded history.

    Shape: every ``perf_budget`` record must carry the full budget key
    set and satisfy the sum-to-wall property (``rel_err ≤ 5%``). Floor:
    per model, the latest MFU-bearing record must be no lower than
    ``band`` × the best MFU ever recorded for that model — wins ratchet
    the floor up; a drop below the band fails. A drop below best but
    inside the band is reported as a warning line (noise allowance).

    ``kind: "perf_ratio"`` records (an interleaved A/B arm's step-time
    ratio) are railed separately, per
    ``(model, arm)`` key: the latest interleaved step-time ratio must be
    no lower than ``band`` × the best ratio ever recorded for that arm —
    a measured compute-tier win (remat policy, scan mode, accumulation)
    becomes a floor the moment it lands. They are excluded from the MFU
    grouping: a ratio record carries no budget or MFU of its own.

    ``kind: "spec_decode"`` records (benchmarks/serving.py spec segment)
    are railed per ``(model, arm)`` against the ABSOLUTE
    :data:`SPEC_DECODE_FLOORS` for their workload arm — repeat_heavy
    ≥ 1.5× plain, adversarial ≥ 0.9× plain — not against a best-ever
    floor: the repeat arm's median swings with n-gram acceptance
    (measured 1.95–2.52 across honest sessions, wider than the MFU
    band), so a best×band ratchet would fail clean readings. A drop
    below the arm's best is reported as a warning drift line. Shape:
    model, a known arm, numeric ratio, ``spec_k ≥ 2``, a ≥3-round
    noise band, positive plain/spec tokens_per_s and ZERO steady-state
    compiles. Also excluded from the MFU grouping.
    Returns ``(ok, messages)``.
    """
    if band is None:
        band = float(os.environ.get(RATCHET_BAND_ENV,
                                    DEFAULT_RATCHET_BAND))
    ok = True
    msgs: List[str] = []
    by_model: Dict[str, List[Dict[str, Any]]] = collections.defaultdict(list)
    by_arm: Dict[Tuple[str, str],
                 List[Dict[str, Any]]] = collections.defaultdict(list)
    by_spec: Dict[Tuple[str, str],
                  List[Dict[str, Any]]] = collections.defaultdict(list)
    for rec in history:
        model = rec.get("model")
        if rec.get("kind") == "perf_ratio":
            ratio = rec.get("ratio")
            if not model or not rec.get("arm") \
                    or not isinstance(ratio, (int, float)):
                ok = False
                msgs.append("FAIL shape [perf_ratio]: record needs "
                            f"model/arm/numeric ratio, got {rec}")
                continue
            by_arm[(model, rec["arm"])].append(rec)
            continue
        if rec.get("kind") == "spec_decode":
            ratio = rec.get("ratio")
            arm = rec.get("arm")
            noise = rec.get("noise") or {}
            tps = rec.get("tokens_per_s") or {}
            shape_ok = (
                bool(model) and arm in SPEC_DECODE_FLOORS
                and isinstance(ratio, (int, float))
                and isinstance(rec.get("spec_k"), int)
                and rec["spec_k"] >= 2
                and isinstance(noise.get("rounds"), int)
                and noise["rounds"] >= 3
                and all(isinstance(noise.get(k), (int, float))
                        for k in ("ratio_min", "ratio_max", "spread"))
                and rec.get("steady_compiles") == 0
                and all(isinstance(tps.get(a), (int, float)) and tps[a] > 0
                        for a in ("plain", "spec")))
            if not shape_ok:
                ok = False
                msgs.append(
                    "FAIL shape [spec_decode]: record needs model, an arm "
                    f"in {sorted(SPEC_DECODE_FLOORS)}, numeric ratio, "
                    "spec_k >= 2, a >=3-round noise band "
                    "(rounds/ratio_min/ratio_max/spread), positive "
                    "plain/spec tokens_per_s and zero steady_compiles, "
                    f"got {rec}")
                continue
            by_spec[(model, arm)].append(rec)
            continue
        if model:
            by_model[model].append(rec)
        if rec.get("kind") != "perf_budget":
            continue
        budget = rec.get("budget_s_per_step") or {}
        missing = [k for k in BUDGET_KEYS if k not in budget]
        if missing:
            ok = False
            msgs.append(f"FAIL shape [{model}]: budget missing "
                        f"categories {missing}")
        err = (rec.get("sum_check") or {}).get("rel_err")
        if err is None or err > SUM_TOLERANCE:
            ok = False
            msgs.append(f"FAIL shape [{model}]: categories sum to wall "
                        f"rel_err={err} > {SUM_TOLERANCE}")
    for model, recs in sorted(by_model.items()):
        with_mfu = [r for r in recs
                    if isinstance(r.get("mfu"), (int, float))]
        if not with_mfu:
            msgs.append(f"ok [{model}]: {len(recs)} record(s), no MFU "
                        "(device peak unknown) — shape-railed only")
            continue
        best = max(r["mfu"] for r in with_mfu)
        latest = with_mfu[-1]["mfu"]
        floor = best * band
        if latest < floor:
            ok = False
            msgs.append(f"FAIL ratchet [{model}]: latest MFU "
                        f"{latest:.4f} < floor {floor:.4f} "
                        f"(best {best:.4f} × band {band})")
        elif latest < best:
            msgs.append(f"warn [{model}]: latest MFU {latest:.4f} below "
                        f"best {best:.4f} but inside the {band} band")
        else:
            msgs.append(f"ok [{model}]: MFU {latest:.4f} is the floor "
                        f"(band {band})")
    for (model, arm), recs in sorted(by_arm.items()):
        best = max(r["ratio"] for r in recs)
        latest = recs[-1]["ratio"]
        floor = best * band
        if latest < floor:
            ok = False
            msgs.append(f"FAIL ratchet [{model}/{arm}]: latest ratio "
                        f"{latest:.4f} < floor {floor:.4f} "
                        f"(best {best:.4f} × band {band})")
        elif latest < best:
            msgs.append(f"warn [{model}/{arm}]: latest ratio "
                        f"{latest:.4f} below best {best:.4f} but inside "
                        f"the {band} band")
        else:
            msgs.append(f"ok [{model}/{arm}]: ratio {latest:.4f} is the "
                        f"floor (band {band})")
    for (model, arm), recs in sorted(by_spec.items()):
        floor_abs = SPEC_DECODE_FLOORS[arm]
        best = max(r["ratio"] for r in recs)
        latest = recs[-1]["ratio"]
        if latest < floor_abs:
            ok = False
            msgs.append(f"FAIL floor [spec_decode {model}/{arm}]: latest "
                        f"spec-vs-plain {latest:.4f} < absolute floor "
                        f"{floor_abs} (the {arm} rail — lossless "
                        "speculation must not cost this much)")
        elif latest < best * band:
            msgs.append(f"warn [spec_decode {model}/{arm}]: latest "
                        f"{latest:.4f} drifted below best {best:.4f} × "
                        f"band {band} (acceptance-driven medians swing "
                        "wider than the MFU band — absolute floor "
                        f"{floor_abs} still holds)")
        else:
            msgs.append(f"ok [spec_decode {model}/{arm}]: {latest:.4f} ≥ "
                        f"floor {floor_abs} (best {best:.4f})")
    return ok, msgs


# ------------------------------------------------------------------ diff

def diff_records(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """Attribute the wall-time delta between two records to the budget
    category that grew the most, and name the top op inside it (ranked by
    its per-op growth where both records carry op tables)."""
    ba = a.get("budget_s_per_step") or {}
    bb = b.get("budget_s_per_step") or {}
    deltas = {cat: round(bb.get(cat, 0.0) - ba.get(cat, 0.0), 6)
              for cat in sorted(set(ba) | set(bb))}
    regressed = max(deltas, key=lambda c: deltas[c]) if deltas else None
    top_op = None
    if regressed:
        tops_b = {t["op"]: t for t in (b.get("top_ops") or {}).get(
            regressed, [])}
        tops_a = {t["op"]: t for t in (a.get("top_ops") or {}).get(
            regressed, [])}
        if tops_b:
            def growth(op):
                before = tops_a.get(op, {}).get("ms_per_step", 0.0)
                return tops_b[op]["ms_per_step"] - before
            top_op = max(tops_b, key=growth)
    return {
        "metric": "perf_diff",
        "model_a": a.get("model"), "model_b": b.get("model"),
        "wall_delta_s_per_step": round(
            (b.get("wall_s_per_step") or 0.0)
            - (a.get("wall_s_per_step") or 0.0), 6),
        "regressed_category": regressed,
        "regressed_delta_s_per_step": deltas.get(regressed, 0.0)
        if regressed else 0.0,
        "top_op": top_op,
        "category_deltas_s_per_step": deltas,
    }


# ------------------------------------------------------------------- CLI

def _select(history: List[Dict[str, Any]], sel: str) -> Dict[str, Any]:
    """A/B selector: an int indexes the history (negatives from the end);
    ``model:idx`` indexes that model's records."""
    if ":" in sel:
        model, _, idx = sel.rpartition(":")
        recs = [r for r in history if r.get("model") == model]
        if not recs:
            raise SystemExit(f"no history records for model {model!r}")
        return recs[int(idx)]
    try:
        return history[int(sel)]
    except (ValueError, IndexError):
        raise SystemExit(
            f"bad selector {sel!r}: use an int index into the history "
            f"({len(history)} records) or model:idx")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m horovod_tpu.tools.perf",
        description="Step-time budgets, MFU ratchet, regression diffs "
                    "(docs/profiling.md)")
    parser.add_argument("--history", default=None,
                        help=f"history file (default: "
                             f"benchmarks/perf_history.jsonl or "
                             f"${HISTORY_ENV})")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_show = sub.add_parser("show", help="print recent budget records")
    p_show.add_argument("--model", default=None)
    p_show.add_argument("-n", type=int, default=1,
                        help="records per model (default 1, newest last)")
    p_diff = sub.add_parser(
        "diff", help="attribute the regression between two records")
    p_diff.add_argument("a")
    p_diff.add_argument("b")
    p_diff.add_argument("--json", action="store_true")
    p_check = sub.add_parser(
        "check", help="shape rail + MFU ratchet (exit 1 on breach)")
    p_check.add_argument("--band", type=float, default=None)
    p_check.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    history = load_history(args.history)
    if args.cmd == "show":
        recs = [r for r in history
                if r.get("kind") == "perf_budget"
                and (args.model is None or r.get("model") == args.model)]
        if not recs:
            print("no budget records in", history_path(args.history))
            return 0
        by_model: Dict[str, List] = collections.defaultdict(list)
        for r in recs:
            by_model[r["model"]].append(r)
        for model in sorted(by_model):
            for r in by_model[model][-max(args.n, 1):]:
                print_budget(r)
        return 0
    if args.cmd == "diff":
        if not history:
            raise SystemExit(f"empty history: {history_path(args.history)}")
        out = diff_records(_select(history, args.a),
                           _select(history, args.b))
        if not args.json:
            print(f"wall {out['wall_delta_s_per_step'] * 1e3:+.3f} ms/step;"
                  f" regressed category: {out['regressed_category']} "
                  f"({out['regressed_delta_s_per_step'] * 1e3:+.3f} "
                  f"ms/step)"
                  + (f"; top op: {out['top_op']}" if out["top_op"]
                     else ""))
            for cat, d in sorted(
                    out["category_deltas_s_per_step"].items(),
                    key=lambda kv: -kv[1]):
                print(f"  {cat:<20} {d * 1e3:+9.3f} ms/step")
        print(json.dumps(out))
        return 0
    if args.cmd == "check":
        ok, msgs = ratchet_check(history, band=args.band)
        if args.json:
            print(json.dumps({"metric": "perf_check", "ok": ok,
                              "messages": msgs}))
        else:
            for m in msgs:
                print(m)
            print("perf check:", "ok" if ok else "FAILED")
        return 0 if ok else 1
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
