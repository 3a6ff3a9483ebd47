"""Contract-registry acceptance (ISSUE 17, tier-1).

Three layers:

1. the FULL ``--contracts --strict`` matrix runs clean on the repo
   as-is (builds are memoized module-wide, so the thin per-family
   drivers here and in test_wire_contracts/test_fusion/
   test_step_builder reuse these builds instead of re-compiling);
2. detection is proven by breaking one contract each way IN-PROCESS —
   drop a donation, emit a stray permute, unpin the DLRM entry layout,
   rank-gate a psum — and asserting exactly the expected finding fires;
3. the CLI exits nonzero NAMING the violated contract, and a real
   subprocess run round-trips the SARIF surface end-to-end.
"""

import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.analysis import analyze_rank_divergence, contracts
from horovod_tpu.analysis.__main__ import main as analysis_main
from horovod_tpu.analysis.hlo import HloCollective, LayoutMove

ALL_FAMILIES = (
    "dp-step-fusion", "dp-step-accum", "gspmd-deferred-every1",
    "gspmd-deferred-programs", "adasum-butterfly", "ring-attention",
    "pipeline-handoff", "hierarchical-allreduce", "decode-tp", "verify-tp",
    "prefill-tp", "decode-tp8", "verify-tp8", "dlrm-layout-pin",
)


def test_registry_covers_required_families():
    fams = contracts.families()
    assert len(fams) >= 8, fams
    for expected in ALL_FAMILIES:
        assert expected in fams, f"{expected} missing from registry"


def test_full_matrix_strict_clean(capsys):
    """Every registered family's contract holds on the repo as-is."""
    rc = analysis_main(["--contracts", "--strict"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "hvd-analyze: clean" in out


@pytest.mark.parametrize("family", ["gspmd-deferred-every1"])
def test_family_contract_holds(family):
    """The families no other test file drives thin. At ``every=1`` the
    deferred factory emits collective HLO signature-identical to the
    standard GSPMD step it wraps (compared post-SPMD-partitioning: GSPMD
    inserts its collectives there, stablehlo alone would compare nothing)."""
    findings = contracts.check_family(family)
    assert not findings, "\n".join(f.format() for f in findings)


# --------------------------------------------------- injected breaks
#
# Each break doctors a MEMOIZED build output (never the repo) and runs
# the family's real verify on it: detection is proven without paying a
# second build, and the break cannot leak — ``summaries()`` still holds
# the pristine dict.

def test_break_dropped_donation_fires():
    base = contracts.summaries("dp-step-accum")
    doctored = dict(base)
    doctored["donated"] = base["accum"]        # the non-donated program
    problems = contracts.get("dp-step-accum").verify(doctored)
    assert problems, "dropped donation went undetected"
    assert any("donat" in p for p in problems), problems


def test_break_stray_permute_fires():
    base = contracts.summaries("decode-tp")
    key = ("llama", 2)
    s = base["summaries"][key]
    perm = HloCollective(
        op="collective_permute", group_size=8,
        groups=(), pairs=tuple((r, (r + 1) % 8) for r in range(8)),
        n_links=8, operand_bytes=512, result_bytes=512,
        ring_bytes=512.0, line=999)
    doctored = {**base, "summaries": {
        **base["summaries"],
        key: s._replace(collectives=s.collectives + (perm,))}}
    problems = contracts.get("decode-tp").verify(doctored)
    assert problems, "stray collective_permute went undetected"
    assert any("collective_permute" in p for p in problems), problems


def test_break_dlrm_layout_unpin_fires():
    base = contracts.summaries("dlrm-layout-pin")
    shape = base["table_shapes"][1]            # per-shard table shape
    s = base["summary"]
    mv = LayoutMove(
        op="transpose", shape=shape, line=42,
        text=f"  %transpose.9 = {shape}{{0,1}} transpose(%param.2)")
    doctored = {**base,
                "summary": s._replace(layout_moves=s.layout_moves + (mv,))}
    problems = contracts.get("dlrm-layout-pin").verify(doctored)
    assert problems, "table-shaped transpose went undetected"
    assert any("entry-layout pin" in p for p in problems), problems


def test_break_rank_gated_psum_fires():
    import analysis_fixture_steps as FS
    findings = analyze_rank_divergence(FS.rank_gated_allreduce_factory, 8)
    assert len(findings) == 1, findings
    f = findings[0]
    assert f.check_id == "jax-rank-divergence"
    assert f.detail["rank_a"] == 0 and f.detail["rank_b"] == 1
    assert f.detail["stream_a"] and not f.detail["stream_b"]


# ----------------------------------------------------------- CLI layer

def test_cli_nonzero_names_violated_contract(capsys):
    """A failing family makes the CLI exit 1 with the contract named in
    the finding line (``contract-<family>``)."""
    base = contracts.summaries("dp-step-accum")
    doctored = dict(base)
    doctored["donated"] = base["accum"]
    fam = "dp-step-accum-injected-break"
    contracts.register(contracts.Contract(
        fam, "injected break (test-only)",
        "horovod_tpu/train/step_builder.py",
        lambda: doctored, contracts.get("dp-step-accum").verify))
    try:
        rc = analysis_main(["--contracts", "--family", fam])
        out = capsys.readouterr().out
        assert rc == 1, out
        assert f"contract-{fam}" in out, out
    finally:
        contracts.unregister(fam)


def test_cli_family_validation(capsys):
    assert analysis_main(["--contracts", "--family", "no-such"]) == 2
    assert "unknown contract families" in capsys.readouterr().err
    assert analysis_main(["--family", "adasum-butterfly"]) == 2
    assert "--family requires --contracts" in capsys.readouterr().err


def test_cli_subprocess_contract_sarif_end_to_end():
    """One real subprocess run (cheap family): exit 0, valid SARIF doc
    with zero results — the CI-annotator surface, end to end."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--contracts",
         "--family", "adasum-butterfly", "--sarif"],
        capture_output=True, text=True, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"] == []
    assert doc["runs"][0]["tool"]["driver"]["name"] == "hvd-analyze"
