"""Compiled-program contract registry (hvd-verify, ISSUE 17).

Every shipped program family registers the machine-checkable invariants
its performance story depends on — the graph-level facts the reference
stack enforces at runtime via the controller's response protocol
(``horovod/common/controller.cc``: coordinated checks that every rank
submitted the same collective over the same payload).  Here the checks
run AHEAD of time against :mod:`horovod_tpu.analysis.hlo` summaries of
the lowered stablehlo / optimized HLO:

- fusion-threshold collective counts + donation (``dp-step-fusion``),
- accumulation's single-allreduce discipline (``dp-step-accum``),
- deferral inertness at ``every=1`` and probe DCE
  (``gspmd-deferred-every1`` / ``gspmd-deferred-programs``),
- ppermute topology × payload × hop-count for the adasum butterfly,
  ring attention, and the pipeline handoff,
- the hierarchical DCN-hop compression byte accounting,
- tensor-parallel decode/verify/prefill wire contracts at tp ∈
  {1, 2, 4, 8} (``2·n_layers`` activation all-reduces and NOTHING else),
- the DLRM entry-layout pin (zero table-shaped transpose/copy).

Builds are memoized per process and cache ONLY summaries and plain
numbers (never live device arrays), so the thin pytest drivers
(tests/test_wire_contracts.py, test_fusion.py, test_step_builder.py,
test_contracts.py) and the full ``--contracts`` matrix share one
build per family.  Violations surface as ``contract-<family>`` ERROR
findings through the same :class:`~.findings.Finding` pipeline as the
lint and jaxpr engines (``--json`` / ``--sarif`` included).
"""

from typing import Any, Callable, Dict, List, NamedTuple, Optional

from .findings import Finding, Severity


class Contract(NamedTuple):
    """One registered program family.

    ``build()`` traces/lowers/compiles the family's programs and returns
    a plain dict of :class:`~.hlo.HloSummary` objects and numbers;
    ``verify(built)`` returns a list of human-readable problem strings
    (empty = contract holds).  ``where`` is the repo-relative source
    file the contract guards — findings anchor there.
    """
    family: str
    description: str
    where: str
    build: Callable[[], Dict[str, Any]]
    verify: Callable[[Dict[str, Any]], List[str]]


_REGISTRY: "Dict[str, Contract]" = {}
_CACHE: Dict[str, Dict[str, Any]] = {}
_PARTS: Dict[str, Any] = {}          # memoized model params (tiny, CPU)


def register(contract: Contract) -> Contract:
    _REGISTRY[contract.family] = contract
    return contract


def unregister(family: str) -> None:
    _REGISTRY.pop(family, None)
    _CACHE.pop(family, None)


def families() -> List[str]:
    return list(_REGISTRY)


def get(family: str) -> Contract:
    return _REGISTRY[family]


def clear_cache() -> None:
    _CACHE.clear()


def summaries(family: str) -> Dict[str, Any]:
    """The family's (memoized) build output."""
    if family not in _CACHE:
        _CACHE[family] = _REGISTRY[family].build()
    return _CACHE[family]


def check_family(family: str) -> List[Finding]:
    """Run one family's contract; each problem → one ERROR finding."""
    c = _REGISTRY[family]
    try:
        built = summaries(family)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:                     # noqa: BLE001 — reported
        return [Finding(
            f"contract-{family}", Severity.ERROR, c.where, 1,
            f"contract build failed: {type(e).__name__}: {e}",
            {"family": family})]
    return [Finding(f"contract-{family}", Severity.ERROR, c.where, 1,
                    problem, {"family": family})
            for problem in c.verify(built)]


def run_contracts(only: Optional[List[str]] = None) -> List[Finding]:
    """Run the whole matrix (or ``only`` the named families)."""
    names = list(only) if only else families()
    unknown = [n for n in names if n not in _REGISTRY]
    if unknown:
        raise KeyError(
            f"unknown contract families {unknown}; "
            f"registered: {families()}")
    _ensure_devices()
    out: List[Finding] = []
    for name in names:
        out.extend(check_family(name))
    return out


def _ensure_devices(n: int = 8) -> None:
    """The matrix traces 8-way meshes — same incantation as tier-1."""
    import jax
    if len(jax.devices()) < n:
        raise SystemExit(
            f"hvd-analyze --contracts needs >= {n} devices "
            f"(got {len(jax.devices())}); run under\n"
            "  JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _reinit(mesh=None, config=None):
    """Fresh hvd engine state for builds that trace through hvd ops."""
    import horovod_tpu as hvd
    hvd.shutdown()
    kw = {}
    if mesh is not None:
        kw["mesh"] = mesh
    if config is not None:
        kw["config"] = config
    hvd.init(**kw)


# --------------------------------------------------------------- helpers

def _mlp64():
    """test_fusion's MLP (width 64, depth 4) — 10 grad leaves."""
    import optax
    from flax import linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            for _ in range(4):
                x = nn.relu(nn.Dense(64)(x))
            return nn.Dense(4)(x)

    def loss_fn(out, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            out, labels).mean()

    return MLP(), loss_fn


def _xent(logits, labels):
    import optax
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def _mlp_small_parts(batch=32):
    """test_step_builder's 16→10 MLP over 4×4×1 images."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from ..optimizer import distributed
    from ..train import create_train_state

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))

    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, 4, 4, 1).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 10, size=(batch,)))
    model = MLP()
    dopt = distributed(optax.sgd(0.1))
    state = create_train_state(model, jax.random.PRNGKey(0), images[:1],
                               dopt)
    return model, dopt, state, images, labels


def _llama8_parts():
    """Memoized decode-contract Llama: heads widened to 8/8 so every
    tp ∈ {1, 2, 4, 8} divides (llama_tiny's 4/2 rejects tp=4 at
    ``validate_tp``)."""
    if "llama8" not in _PARTS:
        import dataclasses
        import jax
        import jax.numpy as jnp
        from flax import linen as nn
        from ..models.llama import Llama, llama_tiny
        cfg = dataclasses.replace(llama_tiny(), n_heads=8, n_kv_heads=8)
        model = Llama(cfg)
        params = nn.meta.unbox(jax.jit(model.init)(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 16), jnp.int32)))["params"]
        _PARTS["llama8"] = (cfg, params)
    return _PARTS["llama8"]


def _mixtral8_parts():
    if "mixtral8" not in _PARTS:
        import dataclasses
        import jax
        import jax.numpy as jnp
        from flax import linen as nn
        from ..models.mixtral import Mixtral, mixtral_tiny
        cfg = dataclasses.replace(mixtral_tiny(), n_heads=8, n_kv_heads=8,
                                  capacity_factor=8.0)
        model = Mixtral(cfg)
        params = nn.meta.unbox(jax.jit(model.init)(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 16), jnp.int32)))["params"]
        _PARTS["mixtral8"] = (cfg, params)
    return _PARTS["mixtral8"]


def _tp_step_summaries(step_kind: str, tps) -> Dict[str, Any]:
    """Lower the tp decode/verify/prefill step per tp and per model kind,
    returning stablehlo summaries keyed ``(kind, tp)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from ..models import decode as MD
    from ..parallel import create_mesh
    from .hlo import summarize_stablehlo

    S, K, T, bs, bmax = 2, 4, 8, 4, 8
    out: Dict[str, Any] = {"summaries": {}}
    kinds = ("llama", "mixtral") if 8 in tps and len(tps) == 1 \
        else ("llama",)
    for kind in kinds:
        cfg, params = (_llama8_parts() if kind == "llama"
                       else _mixtral8_parts())
        out["n_layers"] = cfg.n_layers
        out["dim"] = cfg.dim
        for tp in tps:
            mesh = create_mesh({"tp": tp}, devices=jax.devices()[:tp])
            kp, vp = MD.init_kv_pools(cfg, 16, bs)
            if tp == 8:
                nd = NamedSharding(mesh, MD.kv_pool_spec())
                kp, vp = jax.device_put(kp, nd), jax.device_put(vp, nd)
            if step_kind == "decode":
                step = jax.jit(MD.make_decode_step_tp(cfg, bs, mesh))
                lowered = step.lower(
                    params, kp, vp, jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S, bmax), jnp.int32),
                    jnp.zeros((S,), jnp.bool_))
            elif step_kind == "verify":
                step = jax.jit(MD.make_verify_step_tp(cfg, bs, mesh))
                lowered = step.lower(
                    params, kp, vp, jnp.zeros((S, K), jnp.int32),
                    jnp.zeros((S,), jnp.int32),
                    jnp.zeros((S, bmax), jnp.int32),
                    jnp.zeros((S,), jnp.bool_))
            else:                                       # prefill
                step = jax.jit(MD.make_prefill_tp(cfg, bs, mesh))
                lowered = step.lower(
                    params, kp, vp, jnp.zeros((1, T), jnp.int32),
                    jnp.zeros((T // bs,), jnp.int32))
            out["summaries"][(kind, tp)] = summarize_stablehlo(
                lowered.as_text())
    return out


def _verify_tp_family(built, act_bytes: int) -> List[str]:
    """Shared decode/verify/prefill wire contract: exactly 2·n_layers
    activation all_reduces over the full tp group, nothing else."""
    problems = []
    n = 2 * built["n_layers"]
    for (kind, tp), s in sorted(built["summaries"].items(),
                                key=lambda kv: (kv[0][0], kv[0][1])):
        tag = f"{kind} tp={tp}"
        if s.ops() != ["all_reduce"] * n:
            problems.append(
                f"{tag}: collective stream must be exactly {n} "
                f"all_reduces, got {s.ops()}")
            continue
        for c in s.collectives:
            if c.group_size != tp:
                problems.append(
                    f"{tag}: all_reduce group_size {c.group_size} != "
                    f"tp {tp} (line {c.line})")
            if c.operand_bytes != act_bytes:
                problems.append(
                    f"{tag}: all_reduce operand {c.operand_bytes} B != "
                    f"activation {act_bytes} B (line {c.line})")
            if c.ring_bytes != 2 * (tp - 1) / tp * act_bytes:
                problems.append(
                    f"{tag}: ring wire bytes {c.ring_bytes} off the "
                    f"2(g-1)/g formula (line {c.line})")
        if s.permutes():
            problems.append(
                f"{tag}: {len(s.permutes())} collective_permute(s) — the "
                f"KV pool must stay head-sharded, zero permutes")
    return problems


# ------------------------------------------------------ family: fusion

def _build_dp_step_fusion():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from ..collectives.ops import fusion_threshold_override
    from ..optimizer import distributed
    from ..train import create_train_state, make_train_step
    from .hlo import summarize_stablehlo

    _reinit()
    model, loss_fn = _mlp64()
    xs = jnp.asarray(
        np.random.RandomState(0).randn(16, 8).astype(np.float32))
    ys = jnp.asarray(np.random.RandomState(1).randint(0, 4, size=(16,)))
    out = {}
    # Fresh step per threshold: jit caches lowerings, the override only
    # matters on the first trace of a given step object (test_fusion).
    for key, thr in (("mono", 1 << 62), ("bucketed", 20 << 10),
                     ("per_leaf", 0)):
        opt = distributed(optax.sgd(0.1))
        state = create_train_state(model, jax.random.PRNGKey(0), xs[:2],
                                   opt, broadcast=False)
        step = make_train_step(model, opt, loss_fn, donate=True)
        with fusion_threshold_override(thr):
            out[key] = summarize_stablehlo(
                step.lower(state, xs, ys).as_text())
    return out


def _verify_dp_step_fusion(b) -> List[str]:
    problems = []
    n_mono = b["mono"].count("all_reduce")
    n_buck = b["bucketed"].count("all_reduce")
    n_per = b["per_leaf"].count("all_reduce")
    if n_mono != 2:
        problems.append(
            f"monolithic threshold must fuse to 2 all_reduces (grads + "
            f"loss pmean), got {n_mono}")
    if n_per != 11:
        problems.append(
            f"threshold 0 must emit one all_reduce per grad leaf + loss "
            f"pmean = 11, got {n_per}")
    if not (n_mono < n_buck < n_per):
        problems.append(
            f"bucketed count must sit strictly between monolithic and "
            f"per-leaf: {n_mono} < {n_buck} < {n_per} fails")
    for key in ("mono", "bucketed", "per_leaf"):
        if not b[key].donated:
            problems.append(
                f"buffer donation lost at the {key} fusion threshold")
    return problems


# ------------------------------------------------------- family: accum

def _build_dp_step_accum():
    from ..train import make_train_step
    from .hlo import summarize_optimized

    _reinit()
    model, dopt, state, images, labels = _mlp_small_parts()
    plain = make_train_step(model, dopt, _xent, donate=False)
    accum = make_train_step(model, dopt, _xent, donate=False,
                            accum_steps=2)
    donated = make_train_step(model, dopt, _xent, donate=True,
                              accum_steps=2)
    return {key: summarize_optimized(
                step.lower(state, images, labels).compile().as_text())
            for key, step in (("plain", plain), ("accum", accum),
                              ("donated", donated))}


def _verify_dp_step_accum(b) -> List[str]:
    problems = []
    n_plain = b["plain"].count("all_reduce")
    n_accum = b["accum"].count("all_reduce")
    if n_accum != n_plain:
        problems.append(
            f"accum_steps=2 changed the compiled all-reduce count "
            f"({n_accum} vs plain {n_plain}) — a collective leaked "
            f"inside the microbatch loop (lint-accum-psum-order)")
    if not b["donated"].donated:
        problems.append(
            "donate=True accumulation step lost input_output_alias — "
            "the scan formulation forfeited buffer donation")
    if b["accum"].donated:
        problems.append(
            "donate=False accumulation step unexpectedly aliases "
            "buffers — donation flag is not being honored")
    return problems


# -------------------------------------------- family: deferred every=1

def _collective_sig(summary):
    return sorted((c.op, c.operand_bytes, c.groups)
                  for c in summary.collectives)


def _build_gspmd_deferred_every1():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.llama import LOGICAL_RULES
    from ..models.mixtral import Mixtral, mixtral_tiny
    from ..optimizer import deferred_pair
    from ..parallel import create_mesh
    from ..train import (create_gspmd_train_state,
                         make_gspmd_deferred_train_step,
                         make_gspmd_train_step)
    from .hlo import summarize_optimized

    cfg = mixtral_tiny()
    mesh = create_mesh({"dp": 8})
    model = Mixtral(cfg)
    pair = deferred_pair(1e-3, every=1)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 16)))
    state = create_gspmd_train_state(model, pair.apply,
                                     jax.random.PRNGKey(0), tokens, mesh,
                                     LOGICAL_RULES)
    standard = make_gspmd_train_step(model, pair.apply, mesh,
                                     LOGICAL_RULES, donate=False)
    deferred = make_gspmd_deferred_train_step(model, pair, mesh,
                                              LOGICAL_RULES, donate=False)
    return {
        "standard": summarize_optimized(
            standard.lower(state, tokens).compile().as_text()),
        "deferred": summarize_optimized(
            deferred.lower_apply(state, tokens).compile().as_text()),
    }


def _verify_gspmd_deferred_every1(b) -> List[str]:
    problems = []
    sig_std = _collective_sig(b["standard"])
    sig_dfr = _collective_sig(b["deferred"])
    if not sig_std:
        problems.append(
            "8-way DP standard step compiled with NO collectives — the "
            "parity comparison is vacuous")
    if sig_dfr != sig_std:
        problems.append(
            f"deferred(every=1) apply program's collective signature "
            f"diverged from the standard step: {len(sig_dfr)} vs "
            f"{len(sig_std)} entries — the deferral is no longer "
            f"graph-level inert at k=1")
    return problems


# ------------------------------------------- family: deferred programs

def _build_gspmd_deferred_programs():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core.sentinel import Sentinel
    from ..optimizer import deferred_pair
    from ..parallel import create_mesh
    from ..train import (create_gspmd_train_state,
                         make_gspmd_deferred_train_step, next_token_loss)
    from .hlo import summarize_optimized

    class TinyLM(nn.Module):
        vocab: int = 13

        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(self.vocab, 8)(tokens)
            return nn.Dense(self.vocab)(nn.relu(nn.Dense(8)(x)))

    mesh = create_mesh({"dp": 8})
    model = TinyLM()
    pair = deferred_pair(1e-2, every=2)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(1, 13, size=(8, 6)))
    state = create_gspmd_train_state(model, pair.apply,
                                     jax.random.PRNGKey(0), tokens, mesh,
                                     ())
    s = Sentinel(max_skips=3, max_rollbacks=1,
                 rollback_fn=lambda st: st, evict_fn=lambda a: None)
    step = make_gspmd_deferred_train_step(
        model, pair, mesh, (),
        loss_fn=lambda lg, tk: next_token_loss(lg, tk),
        data_axes=("dp",), donate=False, sentinel=s)
    return {
        "apply": summarize_optimized(
            step.lower_apply(state, tokens).compile().as_text()),
        "skip": summarize_optimized(
            step.lower_skip(state, tokens).compile().as_text()),
        "probe": summarize_optimized(
            step.lower_probe(state, tokens).compile().as_text()),
    }


def _verify_gspmd_deferred_programs(b) -> List[str]:
    problems = []
    for key in ("apply", "skip", "probe"):
        if b[key].n_lines == 0:
            problems.append(f"{key} program compiled to empty HLO")
    if b["probe"].fusion_count > b["apply"].fusion_count:
        problems.append(
            f"probe program has MORE fusions than apply "
            f"({b['probe'].fusion_count} > {b['apply'].fusion_count}) — "
            f"the optimizer.update DCE regressed")
    if b["probe"].n_lines >= b["apply"].n_lines:
        problems.append(
            f"probe program is not strictly smaller than apply "
            f"({b['probe'].n_lines} vs {b['apply'].n_lines} lines) — "
            f"probe DCE regressed")
    return problems


# ------------------------------------------------ family: adasum ring pp

def _build_adasum_butterfly():
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from ..collectives.adasum import _butterfly
    from .hlo import summarize_stablehlo

    _reinit()
    x = jnp.ones((64,), jnp.float32)
    f = jax.jit(shard_map(lambda t: _butterfly(t, hvd.RANK_AXIS),
                          mesh=hvd.mesh(), in_specs=P(), out_specs=P(),
                          check_vma=False))
    return {"summary": summarize_stablehlo(f.lower(x).as_text()),
            "n": 8, "payload": 64 * 4}


def _verify_adasum_butterfly(b) -> List[str]:
    problems = []
    s, n, payload = b["summary"], b["n"], b["payload"]
    perms = s.permutes()
    if len(perms) != 3:                           # log2(8)
        return [f"butterfly must lower to log2({n})=3 permutes, "
                f"got {len(perms)}"]
    for d, c in zip((1, 2, 4), perms):
        if c.operand_bytes != payload or c.ring_bytes != payload:
            problems.append(
                f"butterfly round d={d} must move the FULL working "
                f"buffer ({payload} B), got operand={c.operand_bytes} "
                f"ring={c.ring_bytes}")
        if set(c.pairs) != {(r, r ^ d) for r in range(n)}:
            problems.append(
                f"butterfly round d={d} lost the XOR-partner topology: "
                f"{sorted(c.pairs)}")
        if c.n_links != n:
            problems.append(
                f"butterfly round d={d}: {c.n_links} links != {n}")
    return problems


def _build_ring_attention():
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from ..parallel.ring import ring_attention
    from .hlo import summarize_stablehlo

    _reinit()
    B, T_local, H, D = 1, 4, 2, 8
    q = jnp.ones((B, 8 * T_local, H, D), jnp.float32)
    f = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, hvd.RANK_AXIS,
                                       impl="jnp"),
        mesh=hvd.mesh(),
        in_specs=(P(None, hvd.RANK_AXIS), P(None, hvd.RANK_AXIS),
                  P(None, hvd.RANK_AXIS)),
        out_specs=P(None, hvd.RANK_AXIS), check_vma=False))
    return {"summary": summarize_stablehlo(f.lower(q, q, q).as_text()),
            "n": 8, "shard_bytes": B * T_local * H * D * 4}


def _verify_ring_attention(b) -> List[str]:
    problems = []
    s, n, shard_bytes = b["summary"], b["n"], b["shard_bytes"]
    perms = s.permutes()
    if len(perms) != 2:
        problems.append(
            f"ring attention must rotate exactly K and V (2 permutes "
            f"per trip), got {len(perms)}")
    ring = {(r, (r + 1) % n) for r in range(n)}
    for c in perms:
        if c.operand_bytes != shard_bytes:
            problems.append(
                f"KV rotation payload {c.operand_bytes} B != one local "
                f"shard {shard_bytes} B (line {c.line})")
        if set(c.pairs) != ring:
            problems.append(
                f"KV rotation left the +1 ring: {sorted(c.pairs)}")
    others = [c for c in s.collectives
              if c.op != "collective_permute"]
    if others:
        problems.append(
            f"non-permute collectives ride the ring-attention step: "
            f"{[c.op for c in others]}")
    return problems


def _build_pipeline_handoff():
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from ..parallel.pipeline import pipeline
    from .hlo import summarize_stablehlo

    _reinit()
    M, F = 4, 16
    x = jnp.ones((M, 2, F), jnp.float32)
    params = jnp.ones((F, F), jnp.float32)

    def stage(p, t):
        return jnp.tanh(t @ p)

    f = jax.jit(shard_map(
        lambda p, t: pipeline(stage, p, t, hvd.RANK_AXIS),
        mesh=hvd.mesh(), in_specs=(P(), P()), out_specs=P(),
        check_vma=False))
    return {"summary": summarize_stablehlo(
                f.lower(params, x).as_text()),
            "n": 8, "act_bytes": 2 * F * 4}


def _verify_pipeline_handoff(b) -> List[str]:
    problems = []
    s, n, act = b["summary"], b["n"], b["act_bytes"]
    perms = s.permutes()
    if len(perms) != 1:
        return [f"one handoff permute per schedule tick, "
                f"got {len(perms)}"]
    c = perms[0]
    if c.operand_bytes != act:
        problems.append(
            f"handoff payload {c.operand_bytes} B != one microbatch "
            f"activation {act} B")
    if set(c.pairs) != {(r, (r + 1) % n) for r in range(n)}:
        problems.append(
            f"handoff left the stage i -> i+1 ring: {sorted(c.pairs)}")
    return problems


# ------------------------------------------- family: hierarchical bf16

def _build_hierarchical_allreduce():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd
    from ..collectives import ops
    from ..core.config import Config
    from .hlo import summarize_stablehlo

    out = {"B": 64 * 4}
    x = jnp.asarray(
        np.random.RandomState(5).randn(8, 64).astype(np.float32))
    for key, name in (("off", "none"), ("on", "bf16")):
        m2 = Mesh(np.array(jax.devices()).reshape(2, 4),
                  ("cross", "intra"))
        _reinit(mesh=m2, config=Config(
            hierarchical_allreduce=True, hierarchical_compression=name))
        f = shard_map(lambda t: ops.allreduce(t, hvd.Sum), mesh=m2,
                      in_specs=P(("cross", "intra")),
                      out_specs=P(("cross", "intra")))
        out[key] = summarize_stablehlo(jax.jit(f).lower(x).as_text())
    return out


def _one(summary, op):
    cs = [c for c in summary.collectives if c.op == op]
    return cs[0] if len(cs) == 1 else None


def _verify_hierarchical_allreduce(b) -> List[str]:
    problems = []
    B = b["B"]
    for key in ("off", "on"):
        if set(b[key].ops()) != {"reduce_scatter", "all_reduce",
                                 "all_gather"}:
            return [f"hierarchical ({key}) must lower to exactly "
                    f"reduce_scatter + cross all_reduce + all_gather, "
                    f"got {b[key].ops()}"]
    ar_off, ar_on = _one(b["off"], "all_reduce"), _one(b["on"],
                                                       "all_reduce")
    if ar_off.operand_bytes != B // 4:
        problems.append(
            f"uncompressed DCN hop must carry B/n_intra = {B // 4} B "
            f"f32, got {ar_off.operand_bytes}")
    if ar_on.operand_bytes != B // 4 // 2:
        problems.append(
            f"bf16 compression must halve ONLY the DCN hop to "
            f"{B // 8} B, got {ar_on.operand_bytes}")
    for key in ("off", "on"):
        rs, ag = _one(b[key], "reduce_scatter"), _one(b[key],
                                                      "all_gather")
        if rs.operand_bytes != B or ag.result_bytes != B:
            problems.append(
                f"ICI phases ({key}) must stay f32-sized ({B} B): "
                f"reduce_scatter operand {rs.operand_bytes}, "
                f"all_gather result {ag.result_bytes}")
    return problems


# --------------------------------------------------- family: dlrm pins

def _build_dlrm_layout_pin():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.dlrm import DLRM, build_sparse_training, dlrm_tiny
    from ..models.llama import LOGICAL_RULES
    from ..parallel import create_mesh
    from ..train import rules_for_mesh
    from .hlo import summarize_optimized

    cfg = dlrm_tiny()
    model = DLRM(cfg)
    rng = np.random.RandomState(0)
    B, n = 16, 8
    dense = jnp.asarray(
        rng.randn(B, cfg.dense_features).astype(np.float32))
    sparse = jnp.asarray(
        rng.randint(0, cfg.rows_per_table, (B, cfg.num_tables)))
    labels = jnp.asarray((rng.rand(B) < 0.3).astype(np.float32))
    mesh = create_mesh({"ep": n})
    rules = rules_for_mesh(mesh, LOGICAL_RULES)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), dense, sparse)["params"])
    jitted, dp, tables, accum, opt_state = build_sparse_training(
        model, cfg, mesh, rules, params)
    txt = jitted.lower(dp, tables, accum, opt_state, dense, sparse,
                       labels).compile().as_text()
    nrows = cfg.num_tables * cfg.rows_per_table
    return {"summary": summarize_optimized(txt),
            "table_shapes": (f"f32[{nrows},{cfg.embed_dim}]",
                             f"f32[{nrows // n},{cfg.embed_dim}]")}


def _verify_dlrm_layout_pin(b) -> List[str]:
    problems = []
    s, shapes = b["summary"], b["table_shapes"]
    table_moves = [m for m in s.layout_moves
                   if any(t in m.text for t in shapes)]
    if table_moves:
        problems.append(
            f"{len(table_moves)} table-sized transpose/copy crept back "
            f"into the sparse step (entry-layout pin regressed), first "
            f"at line {table_moves[0].line}: "
            f"{table_moves[0].text.strip()[:120]}")
    n_t = sum(1 for m in s.layout_moves if m.op == "transpose")
    n_c = sum(1 for m in s.layout_moves if m.op == "copy")
    if n_t > 102:
        problems.append(
            f"whole-program transpose budget blown: {n_t} > 102")
    if n_c > 34:
        problems.append(f"whole-program copy budget blown: {n_c} > 34")
    return problems


# --------------------------------------------------------- registration

def _register_builtin() -> None:
    for fam, desc, where, build, verify in (
        ("dp-step-fusion",
         "fusion threshold reshapes the DP gradient collective stream "
         "(2 / bucketed / 11) with donation intact at every threshold",
         "horovod_tpu/collectives/ops.py",
         _build_dp_step_fusion, _verify_dp_step_fusion),
        ("dp-step-accum",
         "gradient accumulation keeps the single-allreduce discipline "
         "and donate=True survives the microbatch scan",
         "horovod_tpu/train/step_builder.py",
         _build_dp_step_accum, _verify_dp_step_accum),
        ("gspmd-deferred-every1",
         "make_gspmd_deferred_train_step(every=1) emits collective HLO "
         "signature-identical to the standard GSPMD step",
         "horovod_tpu/train/gspmd.py",
         _build_gspmd_deferred_every1, _verify_gspmd_deferred_every1),
        ("gspmd-deferred-programs",
         "the deferred x sentinel three-program set keeps probe DCE: "
         "probe strictly smaller than apply",
         "horovod_tpu/train/gspmd.py",
         _build_gspmd_deferred_programs, _verify_gspmd_deferred_programs),
        ("adasum-butterfly",
         "log2(n) full-buffer XOR-partner permute rounds",
         "horovod_tpu/collectives/adasum.py",
         _build_adasum_butterfly, _verify_adasum_butterfly),
        ("ring-attention",
         "exactly the K and V shards rotate the +1 ring, nothing else "
         "rides the step",
         "horovod_tpu/parallel/ring.py",
         _build_ring_attention, _verify_ring_attention),
        ("pipeline-handoff",
         "one activation permute per schedule tick around the stage ring",
         "horovod_tpu/parallel/pipeline.py",
         _build_pipeline_handoff, _verify_pipeline_handoff),
        ("hierarchical-allreduce",
         "bf16 compression halves ONLY the cross-slice (DCN) hop; ICI "
         "reduce-scatter/all-gather stay f32-sized",
         "horovod_tpu/collectives/ops.py",
         _build_hierarchical_allreduce, _verify_hierarchical_allreduce),
        ("decode-tp",
         "tp in {1,2,4}: decode lowers to exactly 2*n_layers [S,D] "
         "activation all_reduces over the full tp group, zero permutes",
         "horovod_tpu/models/decode.py",
         lambda: _tp_step_summaries("decode", (1, 2, 4)),
         lambda b: _verify_tp_family(b, 2 * b["dim"] * 4)),
        ("verify-tp",
         "tp in {1,2,4}: K-wide verify keeps the decode wire contract "
         "at the [S*K,D] window activation",
         "horovod_tpu/models/decode.py",
         lambda: _tp_step_summaries("verify", (1, 2, 4)),
         lambda b: _verify_tp_family(b, 2 * 4 * b["dim"] * 4)),
        ("prefill-tp",
         "tp in {1,2,4}: prefill emits the same 2-per-layer activation "
         "all_reduces at the [1,T,D] width, zero permutes",
         "horovod_tpu/models/decode.py",
         lambda: _tp_step_summaries("prefill", (1, 2, 4)),
         lambda b: _verify_tp_family(b, 8 * b["dim"] * 4)),
        ("decode-tp8",
         "llama + mixtral at tp=8 with device_put pools: the full-mesh "
         "decode wire contract",
         "horovod_tpu/models/decode.py",
         lambda: _tp_step_summaries("decode", (8,)),
         lambda b: _verify_tp_family(b, 2 * b["dim"] * 4)),
        ("verify-tp8",
         "llama + mixtral at tp=8: the K-wide verify wire contract",
         "horovod_tpu/models/decode.py",
         lambda: _tp_step_summaries("verify", (8,)),
         lambda b: _verify_tp_family(b, 2 * 4 * b["dim"] * 4)),
        ("dlrm-layout-pin",
         "compiled sparse DLRM step has zero table-shaped transpose/copy "
         "and stays under the whole-program move budget",
         "horovod_tpu/models/dlrm.py",
         _build_dlrm_layout_pin, _verify_dlrm_layout_pin),
    ):
        register(Contract(fam, desc, where, build, verify))


_register_builtin()
