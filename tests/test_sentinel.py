"""Numeric-integrity sentinel tests (horovod_tpu/core/sentinel.py +
train.py threading; docs/numeric_integrity.md).

Ladder policy is proven with a FAKE clock and zero sleeps (injected
``clock=``; every decision is step-counted). The in-graph health vector,
where-guard skip, and two-program probe dispatch run on the 8-virtual-
device CPU mesh. Multi-process chaos (nan skip across real ranks, desync
eviction through the elastic driver) lives in
tests/test_integration_run.py.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.core import sentinel as sentinel_mod
from horovod_tpu.core.exceptions import HorovodInternalError
from horovod_tpu.core.sentinel import (Health, Sentinel, SentinelAction,
                                       decode_health, health_vector,
                                       param_digest)


# -- helpers ----------------------------------------------------------------

def _health(finite_by_rank, fingerprints=None) -> Health:
    fbr = np.asarray(finite_by_rank, bool)
    fp = (np.zeros(len(fbr), np.uint32) if fingerprints is None
          else np.asarray(fingerprints, np.uint32))
    return Health(finite=bool(fbr.all()), finite_by_rank=fbr,
                  grad_norm=1.0, fingerprints=fp)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _tree():
    rng = np.random.RandomState(0)
    return {"w": jnp.asarray(rng.randn(4, 3).astype(np.float32)),
            "b": jnp.asarray(rng.randn(3).astype(np.float32)),
            "n": jnp.arange(3)}           # int leaf: excluded from digest


# -- health vector / digest -------------------------------------------------

def test_health_vector_shape_and_decode():
    t = _tree()
    raw = jax.jit(lambda g, p: health_vector(g, p))(t, t)
    assert raw.shape == (1, 3)
    h = decode_health(raw)
    assert h.finite and h.finite_by_rank.tolist() == [True]
    manual = float(np.sqrt(sum(
        np.sum(np.square(np.asarray(l, np.float64)))
        for l in (t["w"], t["b"]))))
    assert h.grad_norm == pytest.approx(manual, rel=1e-5)
    assert h.fingerprints.dtype == np.uint32


def test_health_vector_flags_nonfinite():
    t = _tree()
    bad = dict(t, w=t["w"].at[1, 1].set(jnp.nan))
    h = decode_health(jax.jit(lambda g, p: health_vector(g, p))(bad, t))
    assert not h.finite
    inf = dict(t, b=t["b"].at[0].set(jnp.inf))
    h2 = decode_health(jax.jit(lambda g, p: health_vector(g, p))(inf, t))
    assert not h2.finite


def test_param_digest_bit_sensitivity():
    t = _tree()
    d0 = np.asarray(jax.jit(param_digest)(t))
    assert np.asarray(jax.jit(param_digest)(dict(t))) == d0  # deterministic
    flipped = dict(t, w=t["w"].at[0, 0].set(float(t["w"][0, 0]) + 1e-6))
    assert np.asarray(jax.jit(param_digest)(flipped)) != d0
    # int leaves are not part of the digest (replicas may legitimately
    # hold per-rank int state like step counters)
    reint = dict(t, n=t["n"] + 7)
    assert np.asarray(jax.jit(param_digest)(reint)) == d0


def test_fingerprints_compared_as_bits_not_floats():
    """A digest whose bit pattern spells NaN must still compare equal to
    itself across ranks (NaN != NaN as floats — the decode must view
    uint32)."""
    nan_bits = np.float32(np.nan)
    raw = np.asarray([[1.0, 0.5, nan_bits], [1.0, 0.5, nan_bits]],
                     np.float32)
    h = decode_health(raw)
    assert len(np.unique(h.fingerprints)) == 1


def test_health_vector_gathers_per_rank_rows(mesh8):
    """Under shard_map the health vector carries ONE row per rank and a
    per-rank fingerprint lane that exposes replica divergence."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def f(x):
        # x: per-rank shard; use it as both grads and "params" so each
        # rank's fingerprint differs
        return health_vector({"g": x}, {"p": x}, axis=hvd.RANK_AXIS)

    x = jnp.arange(8, dtype=jnp.float32)[:, None]
    raw = jax.jit(shard_map(
        f, mesh=mesh8, in_specs=P(hvd.RANK_AXIS), out_specs=P(),
        check_vma=False))(x)
    assert raw.shape == (8, 3)
    h = decode_health(raw)
    assert h.finite
    assert len(np.unique(h.fingerprints)) == 8   # all replicas distinct


# -- the policy ladder (fake clock, no sleeps) ------------------------------

def test_ladder_skip_then_rollback_then_evict():
    clock = FakeClock()
    evicted = []
    s = Sentinel(max_skips=2, max_rollbacks=1, clock=clock,
                 evict_fn=evicted.append)
    bad = _health([True, False, True, True])     # rank 1 non-finite
    assert s.observe(bad, 1).kind == "skip"
    assert s.observe(bad, 2).kind == "skip"
    assert s.in_containment and s.steps_skipped == 2
    a3 = s.observe(bad, 3)
    assert a3.kind == "rollback" and s.rollbacks == 1
    # rollback resets the consecutive-skip counter: budget refills
    assert s.observe(bad, 4).kind == "skip"
    assert s.observe(bad, 5).kind == "skip"
    a6 = s.observe(bad, 6)
    assert a6.kind == "evict" and a6.rank == 1 and s.evictions == 1
    # history timestamps come from the injected clock, not wall time
    assert all(100.0 < t < 200.0 for (t, *_rest) in s.history)
    assert [k for (_t, k, *_r) in s.history] == [
        "skip", "skip", "rollback", "skip", "skip", "evict"]


def test_ladder_recovers_on_healthy_step():
    s = Sentinel(max_skips=3, max_rollbacks=1, clock=FakeClock())
    bad, ok = _health([False]), _health([True])
    assert s.observe(bad, 1).kind == "skip"
    assert s.in_containment
    assert s.observe(ok, 2).kind == "ok"
    assert not s.in_containment
    # the consecutive counter reset: full skip budget available again
    for step in (3, 4, 5):
        assert s.observe(bad, step).kind == "skip"
    assert s.observe(bad, 6).kind == "rollback"


def test_ladder_abort_when_all_ranks_bad():
    s = Sentinel(max_skips=0, max_rollbacks=0, clock=FakeClock())
    assert s.observe(_health([False, False]), 1).kind == "abort"


def test_ladder_evicts_nonfinite_minority_directly():
    s = Sentinel(max_skips=0, max_rollbacks=0, clock=FakeClock(),
                 evict_fn=lambda a: None)
    a = s.observe(_health([True, True, False, True]), 1)
    assert (a.kind, a.rank) == ("evict", 2)


def test_fingerprint_minority_evicts_immediately():
    """Desync is not skippable: the corrupt replica stays corrupt, so a
    fingerprint minority is evicted on sight — even with skip budget."""
    s = Sentinel(max_skips=5, max_rollbacks=5, clock=FakeClock(),
                 evict_fn=lambda a: None)
    h = _health([True, True, True], fingerprints=[7, 9, 7])
    a = s.observe(h, 4)
    assert (a.kind, a.rank) == ("evict", 1)
    assert s.last_fingerprint_mismatch_step == 4
    assert s.evictions == 1


def test_fingerprint_tie_aborts_not_evicts():
    """1v1 divergence is unattributable — evicting either rank risks
    killing the healthy one; abort to the verified-commit restore."""
    s = Sentinel(clock=FakeClock())
    a = s.observe(_health([True, True], fingerprints=[7, 9]), 1)
    assert a.kind == "abort" and a.rank is None
    assert s.last_fingerprint_mismatch_step == 1


def test_observe_finite_single_rank_ladder():
    s = Sentinel(max_skips=1, max_rollbacks=0, clock=FakeClock())
    assert s.observe_finite(True, 1).kind == "ok"
    assert s.observe_finite(False, 2).kind == "skip"
    assert s.observe_finite(False, 3).kind == "abort"  # n=1: no minority


def test_counters_dict_and_registry(monkeypatch):
    s = Sentinel(clock=FakeClock())
    assert set(s.counters()) == set(sentinel_mod.COUNTER_KEYS)
    monkeypatch.setattr(sentinel_mod, "_active", None)
    zeros = sentinel_mod.counters()
    assert zeros["steps_skipped"] == 0
    assert zeros["last_fingerprint_mismatch_step"] == -1
    sentinel_mod.install(s)
    s.steps_skipped = 5
    assert sentinel_mod.counters()["steps_skipped"] == 5


def test_rollback_without_hook_escalates():
    s = Sentinel(clock=FakeClock())
    with pytest.raises(HorovodInternalError):
        s.do_rollback({"params": 1})


def test_default_evict_outside_driver_escalates(monkeypatch):
    from horovod_tpu.elastic import constants as C
    monkeypatch.delenv(C.COORD_ADDR_ENV, raising=False)
    monkeypatch.delenv(C.WORLD_VERSION_ENV, raising=False)
    with pytest.raises(HorovodInternalError):
        sentinel_mod.default_evict(
            SentinelAction("evict", rank=0, reason="test"))
    with pytest.raises(HorovodInternalError):
        sentinel_mod.default_evict(SentinelAction("abort", reason="test"))


def test_rollback_lands_on_verified_commit(tmp_path):
    """The rollback hook restores through elastic ObjectState commits —
    content-addressed and blake2b-verified at read, so a torn newest
    commit falls back to the previous verified one instead of loading
    garbage."""
    from horovod_tpu import elastic
    from horovod_tpu.elastic import state as state_mod

    st = elastic.ObjectState(commit_dir=str(tmp_path), w=jnp.ones(3),
                             steps=0)
    st.commit()                                   # verified commit #1
    assert st.flush_commits(timeout=30)
    st.w = st.w * 5
    st.steps = 1
    st.commit()                                   # verified commit #2
    assert st.flush_commits(timeout=30)
    # tear a blob unique to the newest commit (truncation: the dominant
    # real-world corruption — the stored digest no longer matches)
    store = state_mod._cas_store(str(tmp_path))
    seqs = store.manifest_seqs()
    m_old = store.read_manifest(min(seqs))
    m_new = store.read_manifest(max(seqs))
    kept = {d for d, _ in m_old["leaves"]} | {m_old["skeleton"]}
    victim = next(d for d, _ in m_new["leaves"] if d not in kept)
    blob = tmp_path / "cas" / "blobs" / victim[:2] / victim
    blob.write_bytes(blob.read_bytes()[:10])

    def rollback_fn(_state):
        fresh = elastic.ObjectState(commit_dir=str(tmp_path),
                                    w=jnp.zeros(3), steps=-1)
        assert fresh.load_latest()
        return fresh

    s = Sentinel(rollback_fn=rollback_fn, clock=FakeClock())
    restored = s.do_rollback(None)
    # fell back to commit #1 (the last verified one), never the torn #2
    np.testing.assert_array_equal(np.asarray(restored.w), np.ones(3))
    assert restored.steps == 0


# -- the jitted step: in-graph guard + two-program probe --------------------

def _xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def _mlp_setup(sentinel, scan_steps=None):
    import flax.linen as nn
    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(16, 4, 4, 1).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=(16,)))
    model = MLP()
    dopt = distributed(optax.sgd(0.1))
    state = create_train_state(model, jax.random.PRNGKey(0), images[:1],
                               dopt)
    step = make_train_step(model, dopt, _xent, sentinel=sentinel,
                           scan_steps=scan_steps)
    return step, state, images, labels


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def _same(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b)))


def test_step_skips_nonfinite_and_recovers():
    s = Sentinel(max_skips=3, max_rollbacks=1, clock=FakeClock())
    step, state, images, labels = _mlp_setup(s)
    state, loss = step(state, images, labels)
    assert np.isfinite(float(loss)) and s.steps_skipped == 0

    # NaN rides rank 0's shard only — the health all_gather makes the
    # verdict global, so the where-guard holds params on EVERY rank.
    bad = images.at[0].set(jnp.nan)
    before = jax.tree_util.tree_map(np.asarray, state.params)
    before_opt = jax.tree_util.tree_map(np.asarray, state.opt_state)
    state, loss = step(state, bad, labels)
    assert s.steps_skipped == 1 and s.in_containment
    assert _same(before, state.params)            # update withheld
    assert _same(before_opt, state.opt_state)
    assert int(state.step) == 2                   # step counter advanced

    # containment: the next (clean) step runs the no-update probe —
    # params still held — and the healthy verdict exits containment
    state, loss = step(state, images, labels)
    assert not s.in_containment
    assert _same(before, state.params)
    # back to normal: the following clean step applies the update
    state, loss = step(state, images, labels)
    assert not _same(before, state.params)
    assert s.steps_skipped == 1                   # no further skips


def test_step_rollback_escalation_uses_hook():
    restored_marker = []

    def rollback_fn(state):
        restored_marker.append(int(np.asarray(state.step)))
        return state

    s = Sentinel(max_skips=1, max_rollbacks=1, clock=FakeClock(),
                 rollback_fn=rollback_fn)
    step, state, images, labels = _mlp_setup(s)
    bad = images.at[0].set(jnp.nan)
    state, _ = step(state, bad, labels)           # skip 1/1
    state, _ = step(state, bad, labels)           # budget out -> rollback
    assert s.rollbacks == 1 and restored_marker == [2]


def test_step_evict_escalation_calls_evict_fn():
    actions = []
    s = Sentinel(max_skips=0, max_rollbacks=0, clock=FakeClock(),
                 evict_fn=actions.append)
    step, state, images, labels = _mlp_setup(s)
    bad = images.at[0].set(jnp.nan)               # rank 0's shard only
    step(state, bad, labels)
    assert len(actions) == 1
    assert actions[0].kind == "evict" and actions[0].rank == 0


def test_probe_program_smaller_than_apply():
    """AOT proof of the two-program trick: the probe lowering carries
    fewer all-reduces than the apply lowering (the gradient allreduce
    feeding the skipped update is DCE'd), and sentinel-on costs exactly
    ONE extra all_gather over sentinel-off."""
    s = Sentinel(clock=FakeClock())
    step_on, state, images, labels = _mlp_setup(s)
    step_off, state_off, _, _ = _mlp_setup(False)

    def count(txt, op):
        return txt.count(f'"stablehlo.{op}"')

    on = step_on.lower(state, images, labels).as_text()
    off = step_off.lower(state_off, images, labels).as_text()
    probe = step_on.lower_probe(state, images, labels).as_text()
    assert count(on, "all_gather") == count(off, "all_gather") + 1
    assert count(probe, "all_reduce") < count(on, "all_reduce")
    # the health probe itself survives in the probe program (it is the
    # program's whole point)
    assert count(probe, "all_gather") == count(on, "all_gather")


def test_sentinel_composes_with_scan_steps():
    """The formerly forbidden combination: with scan_steps=k the inner
    health vectors stack to [k, n, 3], the host ladder adjudicates every
    inner step, and the in-graph where-guard keeps a non-finite inner
    step from touching state even though the host only sees the health
    after the whole folded window."""
    s = Sentinel(max_skips=4, max_rollbacks=1, clock=FakeClock())
    step, state, images, labels = _mlp_setup(s, scan_steps=2)

    state, loss = step(state, images, labels)     # 2 clean inner steps
    assert np.isfinite(float(loss))
    assert int(state.step) == 2 and s.steps_skipped == 0

    # One dispatch = 2 bad inner steps: the ladder observes BOTH stacked
    # health rows (2 skips), and the where-guard held params on each.
    before = jax.tree_util.tree_map(np.asarray, state.params)
    bad = images.at[0].set(jnp.nan)
    state, _ = step(state, bad, labels)
    assert int(state.step) == 4
    assert s.steps_skipped == 2 and s.in_containment
    assert _same(before, state.params)

    # Containment: the next clean dispatch runs the (folded) probe —
    # params still held — and its healthy verdicts exit containment.
    state, _ = step(state, images, labels)
    assert not s.in_containment
    assert _same(before, state.params)

    # Back to normal: the following clean dispatch applies updates.
    state, _ = step(state, images, labels)
    assert not _same(before, state.params)
    assert s.steps_skipped == 2                   # no further skips


def test_gspmd_step_guard_and_probe():
    """GSPMD form: [1,3] health via implicit XLA reductions; skip guard
    and probe dispatch work without a named rank axis."""
    import flax.linen as nn
    from horovod_tpu.train import (create_gspmd_train_state,
                                   make_gspmd_train_step, next_token_loss)

    class TinyLM(nn.Module):
        vocab: int = 13

        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(self.vocab, 8)(tokens)
            return nn.Dense(self.vocab)(nn.relu(nn.Dense(8)(x)))

    # tokens[0,0] == 0 poisons the loss (divide by zero -> inf/nan
    # grads): a deterministic in-graph fault switch
    def loss(logits, tokens):
        trap = jnp.where(tokens[0, 0] == 0, 0.0, 1.0)
        return next_token_loss(logits, tokens) / trap

    from horovod_tpu.parallel import create_mesh
    mesh = create_mesh({"dp": 8})
    model = TinyLM()
    opt = optax.adam(1e-2)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, 13, size=(8, 6)))
    state = create_gspmd_train_state(model, opt, jax.random.PRNGKey(0),
                                     tokens, mesh, ())
    s = Sentinel(max_skips=3, max_rollbacks=1, clock=FakeClock())
    step = make_gspmd_train_step(model, opt, mesh, (), loss_fn=loss,
                                 data_axes=("dp",), sentinel=s)
    state, l0 = step(state, tokens)
    assert np.isfinite(float(l0)) and s.steps_skipped == 0
    before = jax.tree_util.tree_map(np.asarray, state.params)
    bad = tokens.at[0, 0].set(0)
    state, _ = step(state, bad)
    assert s.steps_skipped == 1 and s.in_containment
    assert _same(before, state.params)
    state, _ = step(state, tokens)                # probe, exits containment
    assert not s.in_containment
    assert _same(before, state.params)
    state, _ = step(state, tokens)                # applies again
    assert not _same(before, state.params)


# -- frontends: callbacks + torch seam --------------------------------------

def test_callback_loop_logs_sentinel_counters(monkeypatch):
    from horovod_tpu.callbacks import Callback, CallbackLoop

    seen = {}

    class Probe(Callback):
        def on_batch_end(self, batch, loop, logs):
            seen.update(logs)

    class St:
        params = {}
        opt_state = {}

    s = Sentinel(clock=FakeClock())
    s.steps_skipped = 3
    sentinel_mod.install(s)
    loop = CallbackLoop(St(), [Probe()])
    loop.batch_end(0, {"loss": 1.0})
    assert seen["sentinel/steps_skipped"] == 3
    assert seen["sentinel/last_fingerprint_mismatch_step"] == -1

    # without an active sentinel the logs stay clean
    monkeypatch.setattr(sentinel_mod, "_active", None)
    seen.clear()
    loop.batch_end(1, {"loss": 1.0})
    assert not any(k.startswith("sentinel/") for k in seen)


def test_torch_optimizer_sentinel_skip(monkeypatch):
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvd_torch
    hvd_torch.shutdown()
    hvd_torch.init()
    try:
        model = torch.nn.Linear(3, 1, bias=False)
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.5),
            named_parameters=model.named_parameters())
        s = Sentinel(max_skips=4, max_rollbacks=0, clock=FakeClock())
        sentinel_mod.install(s)
        before = model.weight.detach().clone()
        x = torch.ones(2, 3)
        bad_x = x.clone()
        bad_x[0, 0] = float("nan")                # NaN input -> NaN grads
        model(bad_x).sum().backward()
        opt.step()
        assert s.steps_skipped == 1
        assert torch.equal(model.weight.detach(), before)  # skipped
        opt.zero_grad()
        model(x).sum().backward()
        opt.step()
        assert s.steps_skipped == 1
        assert not torch.equal(model.weight.detach(), before)  # applied
    finally:
        monkeypatch.setattr(sentinel_mod, "_active", None)
        hvd_torch.shutdown()


# -- config / watchdog surfaces ---------------------------------------------

def test_config_env_knobs(monkeypatch):
    from horovod_tpu.core.config import Config
    monkeypatch.setenv("HOROVOD_SENTINEL", "1")
    monkeypatch.setenv("HOROVOD_SENTINEL_MAX_SKIPS", "7")
    monkeypatch.setenv("HOROVOD_SENTINEL_MAX_ROLLBACKS", "2")
    cfg = Config.from_env()
    assert cfg.sentinel and cfg.sentinel_max_skips == 7
    assert cfg.sentinel_max_rollbacks == 2
    s = Sentinel(clock=FakeClock())
    assert s.max_skips == 7 and s.max_rollbacks == 2


def test_env_engages_sentinel_in_step_factory(monkeypatch):
    monkeypatch.setenv("HOROVOD_SENTINEL", "1")
    hvd.shutdown()
    hvd.init()                                    # context re-reads env
    step, state, images, labels = _mlp_setup(None)
    assert isinstance(step.sentinel, Sentinel)
    monkeypatch.setattr(sentinel_mod, "_active", None)


def test_watchdog_heartbeat_reports_sentinel(monkeypatch):
    from horovod_tpu.core import watchdog
    s = Sentinel(clock=FakeClock())
    s.steps_skipped = 2
    sentinel_mod.install(s)
    hb = watchdog.monitor().heartbeat()
    assert hb["sentinel"]["steps_skipped"] == 2
    monkeypatch.setattr(sentinel_mod, "_active", None)


# -- overhead guardrail (slow: excluded from tier-1) ------------------------

@pytest.mark.slow
def test_sentinel_overhead_within_noise():
    """The health probe is three fused elementwise passes + one tiny
    all_gather + a [n,3] host read: its steady-state cost must stay
    inside the noise band. Measured with interleaved rounds (CLAUDE.md:
    never separate blocks) and the median of per-round ratios (robust to
    bursty contention), on a ONE-device mesh: the 8-virtual-device CPU
    mesh replicates every rank's health passes onto the same physical
    cores (8x the real per-chip cost — the shared-cores bias class from
    CLAUDE.md), while on real hardware each rank probes in parallel."""
    import sys
    sys.path.insert(0, "benchmarks")
    import flax.linen as nn
    from jax.sharding import Mesh
    from common import slope_time_paired

    from horovod_tpu.optimizer import distributed
    from horovod_tpu.train import create_train_state, make_train_step

    # A realistically-sized model: the probe cost is O(params) memory
    # traffic, so it must be measured against a step with real compute
    # (on the micro-MLP the fixture uses elsewhere, the probe alone
    # reads as ~30%).
    class Wide(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            for _ in range(3):
                x = nn.relu(nn.Dense(512)(x))
            return nn.Dense(10)(x)

    rng = np.random.RandomState(0)
    B = 512   # compute scales with batch; the probe is O(params) only
    images = jnp.asarray(rng.randn(B, 8, 8, 4).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=(B,)))
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), (hvd.RANK_AXIS,))

    def build(sentinel):
        model = Wide()
        dopt = distributed(optax.sgd(0.1))
        state = create_train_state(model, jax.random.PRNGKey(0),
                                   images[:1], dopt, mesh=mesh1)
        step = make_train_step(model, dopt, _xent, mesh=mesh1,
                               axis_name=hvd.RANK_AXIS, sentinel=sentinel)
        box = {"state": state}

        def fn(k):
            for _ in range(k):
                box["state"], loss = step(box["state"], images, labels)
            jax.block_until_ready(loss)
        return fn

    _slopes, rounds = slope_time_paired(
        {"off": build(False), "on": build(Sentinel(clock=FakeClock()))},
        s_short=4, s_long=12, rounds=7, return_rounds=True)
    ratios = sorted(r["on"] / r["off"] for r in rounds)
    median = ratios[len(ratios) // 2]
    # Measured ~1.02-1.04 (docs/numeric_integrity.md); 0.10 leaves room
    # for the +-10% run-to-run swing CLAUDE.md documents for this host.
    assert abs(median - 1.0) < 0.10, f"sentinel overhead ratio {median:.3f}"
