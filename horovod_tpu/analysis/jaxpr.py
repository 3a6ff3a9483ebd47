"""jaxpr-level collective-graph analyzer.

Traces a step function abstractly (``jax.make_jaxpr`` over
``ShapeDtypeStruct`` args — no device execution; runs on CPU with zero
chips) and walks the closed jaxpr, descending into ``pjit`` / ``scan`` /
``cond`` / ``switch`` / ``while`` / ``shard_map`` sub-jaxprs, to extract
the ordered collective signature stream and run static consistency
checks.  This is the SPMD answer to the reference controller's runtime
negotiation (``horovod/common/controller.cc``): where the reference
detects that ranks submitted different collective streams *while the job
hangs*, GSPMD compiles one program for all ranks — so the only way ranks
can diverge is rank-dependent control flow, which is exactly what these
checks look for *before launch*.

Check ids (documented in docs/analysis.md):

- ``jax-cond-collective`` (ERROR): a collective primitive inside a
  ``lax.cond``/``lax.switch`` branch.  If the predicate is rank-dependent,
  some ranks enter the collective and others do not → deadlock.
- ``jax-grad-psum`` (ERROR): the transposed residue of differentiating
  ``lax.psum`` under ``shard_map(check_vma=False)`` — gradients silently
  scale by the axis size (the trap worked around in
  ``parallel/pipeline.py``: mask per-device, psum AFTER ``grad``).
- ``jax-cond-carry`` (WARNING): large state passed through a cond branch
  unchanged.  ``lax.cond`` cannot alias loop-carried state across the
  branch, so the pass-through is a COPY every step (the trap that killed
  the ``lax.cond`` deferred optimizer — ``optimizer/moe_opt.py``,
  VERDICT r5 #2).
- ``jax-donated-reuse`` (ERROR): a buffer donated to a jitted call is
  used again afterwards — XLA may already have aliased its memory.
- ``jax-unknown-axis`` (ERROR): a collective names an axis that is not in
  the enclosing mesh.
- ``jax-axis-order`` (WARNING): a multi-axis collective lists mesh axes
  out of mesh order, breaking ``collectives/ops.py``'s hierarchical
  ``(cross..., intra)`` convention (intra = last mesh axis rides ICI).
"""

import contextlib
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax
from jax._src import core as _src_core
from jax._src import source_info_util as _source_info

from .findings import Finding, Severity

# Named-axis collective primitives and where each keeps its axis names
# (jax calls the psum-family param "axes", the gather family "axis_name").
# The registry lives next to the data plane so the two stay in lockstep.
from ..collectives.ops import COLLECTIVE_PRIMITIVES as COLLECTIVE_PRIMS
# axis_index is rank-divergent *by design*; it is part of the stream but
# exempt from the cond-collective deadlock check.
_DEADLOCKING = set(COLLECTIVE_PRIMS) - {"axis_index"}

DEFAULT_BIG_CARRY_BYTES = 1 << 20  # 1 MiB


class CollectiveCall(NamedTuple):
    """One entry of the ordered collective signature stream."""
    primitive: str
    axes: Tuple[str, ...]
    shape: Tuple[int, ...]
    dtype: str
    file: str
    line: int


def _loc(eqn) -> Tuple[str, int]:
    frame = _source_info.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "<unknown>", 0
    return frame.file_name, frame.start_line


def _axis_names(eqn) -> Tuple[str, ...]:
    param = COLLECTIVE_PRIMS[eqn.primitive.name]
    axes = eqn.params.get(param, ())
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    # psum's "axes" may mix named axes with positional ints — names only.
    return tuple(a for a in axes if isinstance(a, str))


def _aval_bytes(aval) -> int:
    try:
        size = int(np.prod(aval.shape)) if aval.shape else 1
        return size * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _inner_jaxpr(obj):
    """Unwrap ClosedJaxpr → Jaxpr; pass Jaxpr through; else None."""
    if isinstance(obj, _src_core.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, _src_core.Jaxpr):
        return obj
    return None


def _generic_sub_jaxprs(params):
    """All sub-jaxprs reachable from an eqn's params (any primitive)."""
    subs = []
    for v in params.values():
        j = _inner_jaxpr(v)
        if j is not None:
            subs.append(j)
        elif isinstance(v, (tuple, list)):
            for item in v:
                j = _inner_jaxpr(item)
                if j is not None:
                    subs.append(j)
    return subs


class _Ctx(NamedTuple):
    # (file, line) of the innermost enclosing cond/switch, or None.
    cond_site: Optional[Tuple[str, int]]
    # Ordered axis names of the innermost enclosing mesh, or None if no
    # shard_map has been entered (GSPMD jaxprs carry no named axes).
    mesh_axes: Optional[Tuple[str, ...]]


class _Analysis:
    def __init__(self, big_carry_bytes: int):
        self.big_carry_bytes = big_carry_bytes
        self.stream: List[CollectiveCall] = []
        self.findings: List[Finding] = []

    # -- per-jaxpr dataflow helpers ------------------------------------

    @staticmethod
    def _input_derived(jaxpr) -> set:
        """Vars (transitively) derived from the jaxpr's inputs."""
        derived = {v for v in jaxpr.invars}
        for eqn in jaxpr.eqns:
            if any(isinstance(v, _src_core.Var) and v in derived
                   for v in eqn.invars):
                derived.update(eqn.outvars)
        return derived

    @staticmethod
    def _reaches_output(jaxpr) -> set:
        """Vars whose value (transitively) feeds the jaxpr's outputs."""
        live = {v for v in jaxpr.outvars if isinstance(v, _src_core.Var)}
        for eqn in reversed(jaxpr.eqns):
            if any(v in live for v in eqn.outvars):
                live.update(v for v in eqn.invars
                            if isinstance(v, _src_core.Var))
        return live

    # -- the walk ------------------------------------------------------

    def visit(self, jaxpr, ctx: _Ctx):
        input_derived = self._input_derived(jaxpr)
        reaches_out = self._reaches_output(jaxpr)
        donated_here = {}  # var -> (file, line) of the donating pjit
        psum_records = []  # for the per-scope jax-grad-psum pass

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            file, line = _loc(eqn)

            # jax-donated-reuse: a var used after some earlier pjit in
            # this scope took it as a donated input.
            for v in eqn.invars:
                if isinstance(v, _src_core.Var) and v in donated_here:
                    dfile, dline = donated_here[v]
                    self.findings.append(Finding(
                        "jax-donated-reuse", Severity.ERROR, file, line,
                        f"value used after being donated to the jitted "
                        f"call at {dfile}:{dline}; the donated buffer may "
                        f"already be aliased",
                        {"donated_at": f"{dfile}:{dline}"}))

            if name in COLLECTIVE_PRIMS:
                self._visit_collective(eqn, ctx, input_derived,
                                       reaches_out, file, line,
                                       psum_records)
            elif name == "cond":
                self._visit_cond(eqn, ctx, file, line)
            elif name == "while":
                for key in ("cond_jaxpr", "body_jaxpr"):
                    j = _inner_jaxpr(eqn.params.get(key))
                    if j is not None:
                        self.visit(j, ctx)
            elif name == "scan":
                j = _inner_jaxpr(eqn.params.get("jaxpr"))
                if j is not None:
                    self.visit(j, ctx)
            elif name == "shard_map":
                mesh = eqn.params.get("mesh")
                axes = tuple(getattr(mesh, "axis_names", ()) or ())
                j = _inner_jaxpr(eqn.params.get("jaxpr"))
                if j is not None:
                    self.visit(j, ctx._replace(mesh_axes=axes or None))
            elif name in ("pjit", "jit", "closed_call", "core_call",
                          "custom_jvp_call", "custom_vjp_call",
                          "custom_vjp_call_jaxpr", "remat", "checkpoint"):
                for j in _generic_sub_jaxprs(eqn.params):
                    self.visit(j, ctx)
                donated = eqn.params.get("donated_invars")
                if donated:
                    for v, d in zip(eqn.invars, donated):
                        if d and isinstance(v, _src_core.Var):
                            donated_here[v] = (file, line)
            else:
                for j in _generic_sub_jaxprs(eqn.params):
                    self.visit(j, ctx)

        self._grad_psum_pass(psum_records)

    def _grad_psum_pass(self, psum_records):
        """jax-grad-psum: the transposed residue of differentiating
        ``lax.psum`` under ``shard_map``.

        The backward pass seeds the scalar loss cotangent as the LITERAL
        1.0 and psum's transpose applies psum directly to it — so the
        jaxpr contains ``psum 1.0`` feeding the gradient outputs.  User
        code can never write that eqn: ``lax.psum(<python scalar>)`` is
        constant-folded at trace time, so a literal-operand psum only
        comes from the transpose.  A second signature (for seeds wrapped
        by a convert/mul) is a const-derived psum feeding the outputs
        next to a DEAD input-derived psum over the same axes — the
        orphaned forward half of the differentiated psum.  Both mean
        gradients silently scale by the axis size; ``barrier()``'s
        psum-of-constant never reaches the outputs and a legitimate
        post-grad psum (parallel/pipeline.py) consumes input-derived
        values, so neither trips this.
        """
        for rec in psum_records:
            if not rec["to_outputs"]:
                continue
            suspicious = rec["literal_operand"] or (
                not rec["from_inputs"]
                and any(s is not rec and s["axes"] == rec["axes"]
                        and s["from_inputs"] and not s["to_outputs"]
                        for s in psum_records))
            if suspicious:
                self.findings.append(Finding(
                    "jax-grad-psum", Severity.ERROR,
                    rec["file"], rec["line"],
                    f"psum over {rec['axes']} applied to the cotangent "
                    f"seed (a constant) with its result feeding the "
                    f"gradient outputs — signature of differentiating "
                    f"psum under shard_map: the seed lands once per "
                    f"device and gradients scale by the axis size. Mask "
                    f"per-device and psum AFTER grad (see "
                    f"parallel/pipeline.py)",
                    {"axes": list(rec["axes"])}))

    def _visit_collective(self, eqn, ctx, input_derived, reaches_out,
                          file, line, psum_records):
        name = eqn.primitive.name
        axes = _axis_names(eqn)
        aval = eqn.outvars[0].aval if eqn.outvars else None
        shape = tuple(getattr(aval, "shape", ()) or ())
        dtype = str(getattr(aval, "dtype", "?"))
        self.stream.append(
            CollectiveCall(name, axes, shape, dtype, file, line))

        # jax-cond-collective: deadlock if the branch predicate is
        # rank-dependent — only some ranks reach the collective.
        if ctx.cond_site is not None and name in _DEADLOCKING:
            cfile, cline = ctx.cond_site
            self.findings.append(Finding(
                "jax-cond-collective", Severity.ERROR, file, line,
                f"collective `{name}` over {axes or '(positional)'} inside "
                f"a cond/switch branch (branch at {cfile}:{cline}); if the "
                f"predicate is rank-dependent this deadlocks — hoist the "
                f"collective out of the branch",
                {"cond_at": f"{cfile}:{cline}", "primitive": name}))

        # Record psum facts for the per-scope jax-grad-psum pass.
        if name == "psum":
            operand_vars = [v for v in eqn.invars
                            if isinstance(v, _src_core.Var)]
            psum_records.append({
                "axes": axes,
                "from_inputs": any(v in input_derived
                                   for v in operand_vars),
                "literal_operand": not operand_vars,
                "to_outputs": any(v in reaches_out for v in eqn.outvars),
                "file": file, "line": line,
            })

        # Axis-name checks need a known mesh (shard_map scope).
        if ctx.mesh_axes is not None and axes:
            unknown = [a for a in axes if a not in ctx.mesh_axes]
            if unknown:
                self.findings.append(Finding(
                    "jax-unknown-axis", Severity.ERROR, file, line,
                    f"collective `{name}` names axis(es) {unknown} not in "
                    f"the enclosing mesh {list(ctx.mesh_axes)}",
                    {"unknown": unknown,
                     "mesh_axes": list(ctx.mesh_axes)}))
            elif len(axes) > 1:
                pos = [ctx.mesh_axes.index(a) for a in axes]
                if pos != sorted(pos):
                    self.findings.append(Finding(
                        "jax-axis-order", Severity.WARNING, file, line,
                        f"collective `{name}` lists axes {list(axes)} out "
                        f"of mesh order {list(ctx.mesh_axes)}; the "
                        f"hierarchical convention is (cross..., intra) "
                        f"with intra = the last (ICI-contiguous) mesh "
                        f"axis (collectives/ops.py)",
                        {"axes": list(axes),
                         "mesh_axes": list(ctx.mesh_axes)}))

    def _visit_cond(self, eqn, ctx, file, line):
        branches = eqn.params.get("branches", ())
        # jax-cond-carry: a branch outvar that IS a branch invar is a
        # pass-through — lax.cond cannot alias it, so it is copied every
        # call.  Sum bytes over the worst branch.
        worst = 0
        for br in branches:
            j = _inner_jaxpr(br)
            if j is None:
                continue
            invars = set(j.invars)
            passthrough = [v for v in j.outvars
                           if isinstance(v, _src_core.Var) and v in invars]
            worst = max(worst, sum(_aval_bytes(v.aval)
                                   for v in passthrough))
        if worst >= self.big_carry_bytes:
            self.findings.append(Finding(
                "jax-cond-carry", Severity.WARNING, file, line,
                f"cond branch passes ~{worst / (1 << 20):.1f} MiB of "
                f"carried state through unchanged; lax.cond cannot alias "
                f"across the branch, so this COPIES the state every step "
                f"(the every-k trap — use two jitted programs instead: "
                f"train.make_gspmd_deferred_train_step)",
                {"passthrough_bytes": worst}))
        sub_ctx = ctx._replace(cond_site=(file, line))
        for br in branches:
            j = _inner_jaxpr(br)
            if j is not None:
                self.visit(j, sub_ctx)


def _closed_jaxpr_of(fn, *args, **kwargs):
    return jax.make_jaxpr(fn)(*args, **kwargs)


def analyze_step(fn, *args,
                 mesh=None,
                 big_carry_bytes: int = DEFAULT_BIG_CARRY_BYTES,
                 **kwargs) -> List[Finding]:
    """Statically analyze a step function; returns the findings.

    ``fn`` is traced abstractly with ``jax.make_jaxpr`` — args may be real
    arrays, pytrees, or ``jax.ShapeDtypeStruct`` skeletons; nothing
    executes on any device.  ``mesh`` (optional) supplies the ambient axis
    names for steps whose collectives are NOT wrapped in an in-trace
    ``shard_map`` (axis names are then checked against ``mesh.axis_names``).
    """
    # A concrete mesh is made ambient for the whole trace: a step that
    # would enter set_mesh itself cannot do so under make_jaxpr.
    ambient = jax.sharding.set_mesh(mesh) \
        if isinstance(mesh, jax.sharding.Mesh) else contextlib.nullcontext()
    try:
        with ambient:
            closed = _closed_jaxpr_of(fn, *args, **kwargs)
    except NameError as e:
        # jax raises at trace time for axis names bound nowhere at all
        # ("unbound axis name: X") — fold it into the same finding the
        # walker emits for a wrong name under a known mesh.
        msg = str(e)
        if "axis name" not in msg:
            raise
        code = getattr(fn, "__code__", None)
        return [Finding(
            "jax-unknown-axis", Severity.ERROR,
            getattr(code, "co_filename", "<unknown>"),
            getattr(code, "co_firstlineno", 0),
            f"tracing failed: {msg} — a collective names an axis no "
            f"enclosing mesh/shard_map binds",
            {"trace_error": msg})]
    ana = _Analysis(big_carry_bytes)
    axes = tuple(getattr(mesh, "axis_names", ()) or ()) if mesh is not None \
        else None
    ana.visit(closed.jaxpr, _Ctx(cond_site=None, mesh_axes=axes))
    return ana.findings


def rank_streams(factory, size: int, ranks=None):
    """Per-rank collective signature streams of a rank-parameterized step.

    ``factory(rank, size)`` must return ``(fn, args)`` (or the dict form
    ``{"fn": fn, "args": (...)}``) with the CONCRETE rank/size already
    bound — the closure a launcher builds per process.  Each rank's step
    is traced abstractly and its ordered collective stream extracted;
    a rank whose trace fails contributes the sentinel stream
    ``[("<trace-error>", message)]`` so rank-DEPENDENT trace failure
    registers as divergence while a uniform failure does not.
    """
    if ranks is None:
        ranks = range(size)
    streams = {}
    for rank in ranks:
        spec = factory(rank, size)
        if isinstance(spec, dict):
            fn, args = spec["fn"], tuple(spec.get("args", ()))
        else:
            fn, args = spec[0], tuple(spec[1])
        try:
            closed = _closed_jaxpr_of(fn, *args)
        except Exception as e:  # noqa: BLE001 — any trace failure counts
            streams[rank] = [("<trace-error>",
                              f"{type(e).__name__}: {e}")]
            continue
        ana = _Analysis(DEFAULT_BIG_CARRY_BYTES)
        ana.visit(closed.jaxpr, _Ctx(cond_site=None, mesh_axes=None))
        streams[rank] = ana.stream
    return streams


def _stream_sig(entry):
    """Comparison key for one stream entry: (primitive, axes, shape,
    dtype) — file/line excluded (identical code traced from different
    closures may report different lines)."""
    if isinstance(entry, CollectiveCall):
        return (entry.primitive, entry.axes, entry.shape, entry.dtype)
    return tuple(entry)  # the ("<trace-error>", msg) sentinel


def _stream_repr(stream) -> List[str]:
    out = []
    for entry in stream:
        if isinstance(entry, CollectiveCall):
            out.append(f"{entry.primitive}{list(entry.axes)} "
                       f"{entry.dtype}{list(entry.shape)}")
        else:
            out.append(f"{entry[0]} {entry[1]}")
    return out


def analyze_rank_divergence(factory, size: int,
                            ranks=None) -> List[Finding]:
    """Static cross-rank divergence detection — the SPMD analogue of the
    reference controller's mismatch response (``horovod/common/
    controller.cc`` builds a "who disagreed, about what" error when
    ranks negotiate different tensor streams; SURVEY.md §2).

    Evaluates the step once per simulated rank with concrete rank/size
    bindings (see :func:`rank_streams`), then diffs the per-rank
    collective signature streams pairwise against rank ``ranks[0]``.
    The first divergent op — extra, missing, or different — produces a
    ``jax-rank-divergence`` ERROR carrying BOTH ranks' full streams and
    the divergence index, catching ``if rank == 0: allreduce(...)``
    before a multi-host job hangs on it.
    """
    streams = rank_streams(factory, size, ranks)
    order = list(streams)
    base_rank = order[0]
    base = streams[base_rank]
    base_sig = [_stream_sig(e) for e in base]
    findings: List[Finding] = []
    for rank in order[1:]:
        other = streams[rank]
        other_sig = [_stream_sig(e) for e in other]
        if other_sig == base_sig:
            continue
        idx = next((i for i, (a, b)
                    in enumerate(zip(base_sig, other_sig)) if a != b),
                   min(len(base_sig), len(other_sig)))
        # Location: the first entry present at the divergence point.
        file, line = "<unknown>", 0
        for stream in (base, other):
            if idx < len(stream) \
                    and isinstance(stream[idx], CollectiveCall):
                file, line = stream[idx].file, stream[idx].line
                break
        a = base_sig[idx] if idx < len(base_sig) else None
        b = other_sig[idx] if idx < len(other_sig) else None
        findings.append(Finding(
            "jax-rank-divergence", Severity.ERROR, file, line,
            f"ranks {base_rank} and {rank} (of {size}) emit different "
            f"collective streams — first divergence at op {idx}: rank "
            f"{base_rank} issues {a}, rank {rank} issues {b}; on a real "
            f"job the minority rank never shows up and the collective "
            f"deadlocks (the mismatch the reference controller "
            f"negotiates at runtime, controller.cc)",
            {"size": size, "divergence_index": idx,
             "rank_a": base_rank, "rank_b": rank,
             "stream_a": _stream_repr(base),
             "stream_b": _stream_repr(other)}))
        break  # first divergent pair is the actionable one
    return findings


def collective_stream(fn, *args, **kwargs) -> List[CollectiveCall]:
    """The ordered collective signature stream of a traced step.

    The static analogue of what the reference controller negotiates at
    runtime: (primitive, axis names, shape, dtype) in program order.
    Comparing two ranks' streams is what ``tools/mismatch.py`` does with
    runtime digests; under GSPMD one trace serves all ranks, so the
    stream doubles as a golden signature for regression tests.
    """
    closed = _closed_jaxpr_of(fn, *args, **kwargs)
    ana = _Analysis(DEFAULT_BIG_CARRY_BYTES)
    ana.visit(closed.jaxpr, _Ctx(cond_site=None, mesh_axes=None))
    return ana.stream
