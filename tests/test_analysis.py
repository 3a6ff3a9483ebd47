"""hvd-analyze: fixture corpus, zero-false-positive sweep, CLI, preflight.

The jaxpr engine must flag every known-bad step in
``tests/analysis_fixture_steps.py`` with exactly its check id and
file:line, and report ZERO findings on the repo's own shipped train
steps and parallel modules.  The AST lint must flag every file in
``tests/analysis_fixtures/`` and come back clean on the repo itself
(``--self-lint`` — this test keeps that pass inside tier-1).

Everything here runs under the CPU conftest mesh; the analyzer itself
never executes device code (jaxpr/AST only).
"""

import json
import os
import subprocess
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.analysis import (Finding, Severity,
                                  analyze_rank_divergence, analyze_step,
                                  collective_stream, findings_from_sarif,
                                  lint_paths, lint_source,
                                  summarize_stablehlo, to_sarif)
from horovod_tpu.analysis.__main__ import main as analysis_main

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(TESTS_DIR)
FIXTURE_STEPS = os.path.join(TESTS_DIR, "analysis_fixture_steps.py")
FIXTURE_DIR = os.path.join(TESTS_DIR, "analysis_fixtures")

sys.path.insert(0, TESTS_DIR)
import analysis_fixture_steps as fixture_steps  # noqa: E402


def _marker_line(path, check_id):
    marker = f"# <- {check_id}"
    with open(path, "r", encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, 1):
            if marker in line:
                return lineno
    raise AssertionError(f"no {marker!r} marker in {path}")


# ---------------------------------------------------------------- jaxpr

JAXPR_CASES = [
    ("cond_psum_spec", "jax-cond-collective"),
    ("grad_psum_spec", "jax-grad-psum"),
    ("cond_carry_spec", "jax-cond-carry"),
    ("bad_axis_spec", "jax-unknown-axis"),
    ("axis_order_spec", "jax-axis-order"),
    ("donated_reuse_spec", "jax-donated-reuse"),
]


@pytest.mark.parametrize("spec_name,check_id", JAXPR_CASES)
def test_fixture_step_flagged(spec_name, check_id):
    """Each known-bad step produces EXACTLY its finding, located at the
    marked line of the fixture module."""
    fn, args = getattr(fixture_steps, spec_name)()
    findings = analyze_step(fn, *args)
    assert [f.check_id for f in findings] == [check_id], findings
    f = findings[0]
    assert f.file == FIXTURE_STEPS
    assert f.line == _marker_line(FIXTURE_STEPS, check_id)
    assert f.severity.value in ("error", "warning")
    # machine-readable round trip
    d = f.to_dict()
    assert d["check_id"] == check_id and d["line"] == f.line


def test_collective_stream_signature():
    """The extracted stream records (primitive, axes, shape, dtype) in
    program order — the static analogue of the reference controller's
    negotiated tensor stream."""
    fn, args = fixture_steps.axis_order_spec()
    stream = collective_stream(fn, *args)
    assert [c.primitive for c in stream] == ["psum"]
    assert stream[0].axes == ("mp", "dp")
    assert stream[0].dtype == "float32"


def test_fixture_corpus_via_cli():
    """`python -m horovod_tpu.analysis --step` flags a fixture spec with
    the right check id and exits 1 (ERROR severity)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.analysis", "--json",
         "--step", f"{FIXTURE_STEPS}:cond_psum_spec"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=180)
    assert proc.returncode == 1, proc.stderr
    records = [json.loads(l) for l in proc.stdout.splitlines() if l]
    assert [r["check_id"] for r in records] == ["jax-cond-collective"]
    assert records[0]["file"] == FIXTURE_STEPS
    assert records[0]["severity"] == "error"


# --------------------------------------------- zero-false-positive sweep

def test_sweep_gspmd_train_steps_clean():
    """The shipped GSPMD train steps (plain and two-program deferred)
    analyze clean — no findings at all."""
    from horovod_tpu.models.llama import LOGICAL_RULES
    from horovod_tpu.models.mixtral import Mixtral, mixtral_tiny
    from horovod_tpu.optimizer import deferred_pair
    from horovod_tpu.parallel import create_mesh
    from horovod_tpu.train import (create_gspmd_train_state,
                                   make_gspmd_deferred_train_step,
                                   make_gspmd_train_step)

    cfg = mixtral_tiny()
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    model = Mixtral(cfg)
    pair = deferred_pair(1e-3, every=2)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)))
    state = create_gspmd_train_state(model, pair.apply,
                                     jax.random.PRNGKey(0),
                                     tokens, mesh, LOGICAL_RULES)

    plain = make_gspmd_train_step(model, pair.apply, mesh, LOGICAL_RULES,
                                  donate=False)
    assert analyze_step(plain, state, tokens, mesh=mesh) == []

    deferred = make_gspmd_deferred_train_step(model, pair, mesh,
                                              LOGICAL_RULES, donate=False)
    # dispatches host-side between two programs; both must be clean
    assert analyze_step(deferred, state, tokens, mesh=mesh) == []


def test_sweep_parallel_modules_clean():
    """parallel/: the pipeline's psum-AFTER-grad pattern and the ring's
    switch-with-collectives-outside must NOT trip the analyzer."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from horovod_tpu.parallel.pipeline import pipeline_value_and_grad
    from horovod_tpu.parallel.ring import ring_attention

    devs = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devs, ("pp",))

    def stage_fn(W, x):
        return jnp.tanh(x @ W)

    def loss_fn(y, t):
        return jnp.mean((y - t) ** 2)

    vg = pipeline_value_and_grad(stage_fn, loss_fn, "pp")

    def pipeline_fn(Ws, xs, ts):
        def body(W, x, t):
            loss, g = vg(W[0], x, t)
            return loss[None], g[None]
        return shard_map(body, mesh=mesh,
                         in_specs=(P("pp"), P(), P()),
                         out_specs=(P("pp"), P("pp")),
                         check_vma=False)(Ws, xs, ts)

    Ws = jax.ShapeDtypeStruct((8, 4, 4), jnp.float32)
    xs = jax.ShapeDtypeStruct((16, 2, 4), jnp.float32)
    ts = jax.ShapeDtypeStruct((16, 2, 4), jnp.float32)
    assert analyze_step(pipeline_fn, Ws, xs, ts) == []

    def ring_fn(q, k, v):
        def inner(qb, kb, vb):
            return ring_attention(qb, kb, vb, "pp", causal=True)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "pp"),) * 3,
                         out_specs=P(None, "pp"), check_vma=False)(q, k, v)

    qkv = jax.ShapeDtypeStruct((2, 32, 4, 8), jnp.float32)
    assert analyze_step(ring_fn, qkv, qkv, qkv) == []


def test_sweep_collectives_barrier_clean():
    """barrier()'s psum-of-constant (result unused) must not be mistaken
    for the grad-psum trap."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("ranks",))

    def fn(x):
        def inner(v):
            hvd.barrier(axis_name="ranks")
            return v * 2
        return shard_map(inner, mesh=mesh, in_specs=P("ranks"),
                         out_specs=P("ranks"), check_vma=False)(x)

    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    assert analyze_step(fn, x) == []


# ----------------------------------------------------------- trap lint

LINT_CASES = [
    ("bad_xla_flags.py", "lint-xla-flags", "error"),
    ("bad_torch_seed.py", "lint-torch-seed", "warning"),
    ("bad_slope_cadence.py", "lint-slope-cadence", "warning"),
    ("bad_silent_rpc.py", "lint-silent-rpc", "warning"),
    ("bad_unguarded_apply.py", "jax-unguarded-apply", "warning"),
    ("bad_monolithic_psum.py", "lint-monolithic-psum", "warning"),
    ("bad_accum_psum_order.py", "lint-accum-psum-order", "warning"),
    ("bad_unbounded_poll.py", "lint-unbounded-poll", "warning"),
    ("bad_blocking_telemetry.py", "lint-blocking-telemetry", "warning"),
    ("bad_blocking_commit.py", "lint-blocking-commit", "warning"),
    ("bad_decode_host_sync.py", "lint-decode-host-sync", "warning"),
    ("bad_host_draft_loop.py", "lint-host-draft-loop", "warning"),
    ("bad_recompile_request_path.py", "lint-recompile-in-request-path",
     "warning"),
    ("bad_xplane_umbrella.py", "lint-xplane-umbrella", "warning"),
    ("bad_replicated_kv_pool.py", "lint-replicated-kv-pool", "warning"),
    ("bad_rank_conditional_collective.py",
     "lint-rank-conditional-collective", "error"),
    ("bad_unverified_peer_blob.py", "lint-unverified-peer-blob", "warning"),
    ("bad_unbounded_admission.py", "lint-unbounded-admission", "warning"),
    ("bad_heavy_signal_handler.py", "lint-heavy-signal-handler", "warning"),
]


@pytest.mark.parametrize("fname,check_id,severity", LINT_CASES)
def test_lint_fixture_flagged(fname, check_id, severity):
    path = os.path.join(FIXTURE_DIR, fname)
    findings = lint_paths([path])
    assert [f.check_id for f in findings] == [check_id], findings
    f = findings[0]
    assert f.line == _marker_line(path, check_id)
    assert f.severity.value == severity


def test_lint_suppression_pragma():
    src = ('import os\n'
           'os.environ["XLA_FLAGS"] = "--xla_bogus=1"  # hvd-analyze: ok\n')
    assert lint_source(src) == []
    src_no_pragma = src.replace("  # hvd-analyze: ok", "")
    assert [f.check_id for f in lint_source(src_no_pragma)] \
        == ["lint-xla-flags"]


def test_lint_guarded_and_safe_flags_clean():
    guarded = (
        'import os\n'
        'if os.environ.get("HOROVOD_FUSION_APPLY_XLA_FLAGS", "") == "1":\n'
        '    os.environ["XLA_FLAGS"] = "--xla_gpu_whatever=1"\n')
    assert lint_source(guarded) == []
    safe = ('import os\n'
            'os.environ["XLA_FLAGS"] = '
            '"--xla_force_host_platform_device_count=8"\n')
    assert lint_source(safe) == []


def test_self_lint_clean(capsys):
    """The repo's own sources pass the trap lint — and the pass stays
    inside tier-1 via this test."""
    rc = analysis_main(["--self-lint"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "hvd-analyze: clean" in out


def test_self_lint_sweeps_benchmarks_and_examples():
    """The --self-lint path set is pinned: benchmarks/ and examples/
    (home of the measurement-trap lints' real targets) must stay in the
    sweep alongside the package, tests, and entry points."""
    from horovod_tpu.analysis.__main__ import REPO_SELF_LINT_TARGETS
    for target in ("horovod_tpu", "tests", "benchmarks", "examples",
                   "__graft_entry__.py"):
        assert target in REPO_SELF_LINT_TARGETS, target


# ---------------------------------------------------- rank divergence

def test_rank_divergence_flags_rank_gated_allreduce():
    """The ISSUE 17 acceptance case: ``if rank == 0: psum(...)`` is
    invisible to a single abstract trace (Python already picked the
    branch) but the per-rank replay catches it — first divergent op,
    BOTH ranks' streams in the detail, location at the gated psum."""
    findings = analyze_rank_divergence(
        fixture_steps.rank_gated_allreduce_factory, 8)
    assert [f.check_id for f in findings] == ["jax-rank-divergence"], \
        findings
    f = findings[0]
    assert f.severity == Severity.ERROR
    assert f.file == FIXTURE_STEPS
    assert f.line == _marker_line(FIXTURE_STEPS, "jax-rank-divergence")
    d = f.detail
    assert d["size"] == 8 and d["divergence_index"] == 0
    assert d["rank_a"] == 0 and d["rank_b"] == 1
    assert d["stream_a"] and d["stream_b"] == []


def test_rank_divergence_zero_false_positives():
    """Every shipped fixture step, wrapped in a rank-ignoring factory,
    plus the uniform-collective control: ZERO divergence findings.
    ``bad_axis_spec`` fails to trace on EVERY rank — uniform failure is
    agreement, not divergence."""
    for spec_name, _ in JAXPR_CASES:
        spec = getattr(fixture_steps, spec_name)
        assert analyze_rank_divergence(
            lambda rank, size, _s=spec: _s(), 4) == [], spec_name
    assert analyze_rank_divergence(
        fixture_steps.uniform_allreduce_factory, 8) == []


# --------------------------------------- hlo layer vs jaxpr layer

_JAXPR_TO_HLO = {"psum": "all_reduce", "pmean": "all_reduce",
                 "ppermute": "collective_permute",
                 "all_gather": "all_gather",
                 "psum_scatter": "reduce_scatter",
                 "all_to_all": "all_to_all"}


def test_hlo_stream_matches_jaxpr_stream():
    """Property: on a program where XLA introduces no extra collectives
    (shard_map lowered to stablehlo, pre-SPMD), the hlo-layer stream is
    the jaxpr-layer stream under the primitive→opcode map — the two
    engines agree on what rides the fabric, in order."""
    from jax import lax, shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]), ("r",))
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def fn(x):
        def inner(v):
            v = lax.psum(v, "r")
            v = lax.ppermute(v, "r", perm)
            g = lax.all_gather(v, "r")
            return jnp.sum(g, axis=0)
        return shard_map(inner, mesh=mesh, in_specs=P("r"),
                         out_specs=P("r"), check_vma=False)(x)

    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    jaxpr_ops = [_JAXPR_TO_HLO[c.primitive]
                 for c in collective_stream(fn, x)]
    summary = summarize_stablehlo(jax.jit(fn).lower(x).as_text())
    assert summary.ops() == jaxpr_ops
    assert jaxpr_ops == ["all_reduce", "collective_permute", "all_gather"]


# --------------------------------------------------------------- SARIF

def test_sarif_round_trip():
    """to_sarif emits schema-shaped 2.1.0 (rules in first-seen order,
    startLine clamped to >= 1, severity→level mapped) and
    findings_from_sarif reconstructs the EXACT finding list from the
    stashed properties.hvd payload — including line 0 and detail."""
    findings = [
        Finding("lint-xla-flags", Severity.ERROR, "a.py", 3, "boom",
                {"flag": "--xla_bogus"}),
        Finding("contract-decode-tp", Severity.ERROR,
                "horovod_tpu/models/decode.py", 0, "stream reshaped"),
        Finding("lint-torch-seed", Severity.WARNING, "b.py", 9, "races"),
        Finding("jax-rank-divergence", Severity.INFO, "c.py", 1, "note"),
    ]
    doc = json.loads(json.dumps(to_sarif(findings)))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "hvd-analyze"
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] == [
        "lint-xla-flags", "contract-decode-tp", "lint-torch-seed",
        "jax-rank-divergence"]
    res = run["results"]
    assert [r["level"] for r in res] == ["error", "error", "warning",
                                         "note"]
    # line-0 registry finding: clamped in the SARIF region ...
    assert res[1]["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 1
    # ... but preserved through the round trip.
    assert findings_from_sarif(doc) == findings


def test_cli_sarif_flag(capsys):
    """--sarif prints ONE SARIF document (not JSON lines) and keeps the
    ERROR exit code."""
    rc = analysis_main(
        ["--sarif", os.path.join(FIXTURE_DIR, "bad_xla_flags.py")])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    results = doc["runs"][0]["results"]
    assert [r["ruleId"] for r in results] == ["lint-xla-flags"]
    assert results[0]["properties"]["hvd"]["severity"] == "error"


# ----------------------------------------------------------- preflight

def test_preflight_blocks_bad_script(tmp_path, monkeypatch):
    """HOROVOD_PREFLIGHT_ANALYZE=1 aborts the launch on ERROR findings;
    unset, the launcher never runs the analyzer."""
    from horovod_tpu.runner.launch import _maybe_preflight_analyze

    bad = tmp_path / "train_bad.py"
    bad.write_text('import os\n'
                   'os.environ["XLA_FLAGS"] = "--xla_bogus_combiner=1"\n')

    monkeypatch.delenv("HOROVOD_PREFLIGHT_ANALYZE", raising=False)
    _maybe_preflight_analyze(["python", str(bad)])  # no-op when unset

    monkeypatch.setenv("HOROVOD_PREFLIGHT_ANALYZE", "1")
    monkeypatch.setenv("PYTHONPATH", REPO_ROOT)
    with pytest.raises(SystemExit, match="preflight analyze"):
        _maybe_preflight_analyze(["python", str(bad)])

    # warn mode reports but does not abort
    monkeypatch.setenv("HOROVOD_PREFLIGHT_ANALYZE", "warn")
    _maybe_preflight_analyze(["python", str(bad)])


def test_preflight_runs_hvd_analyze_hook(tmp_path, monkeypatch):
    """A script exposing an HVD_ANALYZE factory gets its step jaxpr-
    checked by the preflight (here: the cond-collective deadlock)."""
    from horovod_tpu.runner.launch import _maybe_preflight_analyze

    script = tmp_path / "train_cond.py"
    script.write_text(
        'import sys\n'
        f'sys.path.insert(0, {TESTS_DIR!r})\n'
        'from analysis_fixture_steps import cond_psum_spec\n'
        'HVD_ANALYZE = cond_psum_spec\n'
        'if __name__ == "__main__":\n'
        '    raise SystemExit("worker body must not run in preflight")\n')

    monkeypatch.setenv("HOROVOD_PREFLIGHT_ANALYZE", "1")
    monkeypatch.setenv("PYTHONPATH", REPO_ROOT)
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    with pytest.raises(SystemExit, match="preflight analyze"):
        _maybe_preflight_analyze(["python", str(script)])


def test_preflight_contracts_mode_command(tmp_path, monkeypatch):
    """HOROVOD_PREFLIGHT_ANALYZE=contracts appends --contracts to the
    preflight subprocess and gives it the 8-virtual-device incantation
    (command construction only — the real matrix runs in
    tests/test_contracts.py, not here)."""
    from horovod_tpu.runner import launch as launch_mod

    script = tmp_path / "train.py"
    script.write_text("print('worker')\n")
    captured = {}

    def fake_run(cmd, env=None, capture_output=None, text=None):
        captured["cmd"], captured["env"] = cmd, env

        class Result:
            returncode = 0
            stdout = ""
            stderr = ""
        return Result()

    monkeypatch.setattr(launch_mod.subprocess, "run", fake_run)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setenv("HOROVOD_PREFLIGHT_ANALYZE", "contracts")
    launch_mod._maybe_preflight_analyze(["python", str(script)])
    assert "--preflight" in captured["cmd"]
    assert "--contracts" in captured["cmd"]
    assert captured["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" \
        in captured["env"]["XLA_FLAGS"]

    # plain "1" keeps the fast lint+jaxpr preflight: no --contracts.
    monkeypatch.setenv("HOROVOD_PREFLIGHT_ANALYZE", "1")
    launch_mod._maybe_preflight_analyze(["python", str(script)])
    assert "--contracts" not in captured["cmd"]


# ------------------------------------------- deferred-step resume phase

class _FakeState(NamedTuple):
    step: int


def test_dispatch_resume_phase():
    """ADVICE r5 #2: the apply-vs-skip counter seeds from state.step on
    first call, so a checkpoint/elastic resume keeps cadence phase
    instead of restarting the window. Exercised at the make_dispatch
    level — the single dispatcher every deferred factory now routes
    through."""
    from horovod_tpu.train import make_dispatch

    calls = []

    def prog(tag):
        def fn(state, tokens):
            calls.append(tag)
            return _FakeState(state.step + 1), 0.0
        return fn

    programs = {"apply": prog("apply"), "skip": prog("skip")}

    # Fresh start: applies land when the global step hits 3, 6, ...
    step = make_dispatch(programs, every=3)
    st = _FakeState(0)
    for _ in range(6):
        st, _loss = step(st, None)
    assert calls == ["skip", "skip", "apply", "skip", "skip", "apply"]

    # Resume mid-window at step=4: the next apply must land at global
    # step 6 (2 steps later), NOT 3 steps later.
    calls.clear()
    step = make_dispatch(programs, every=3)
    st = _FakeState(4)
    for _ in range(4):
        st, _loss = step(st, None)
    assert calls == ["skip", "apply", "skip", "skip"]
    assert st.step == 8

    # Folded scan advances the counter by k per dispatch: every=2 with
    # scan_steps=2 applies on EVERY dispatch (each covers a full window).
    calls.clear()
    step = make_dispatch(programs, every=2, scan_steps=2)
    st = _FakeState(0)
    for _ in range(3):
        st, _loss = step(st, None)
    assert calls == ["apply", "apply", "apply"]
