"""The traced window's leaf operations joined with the step program's map of
itself, so that a reader can ask for device time by phase or by model scope.

The ``hvd_*`` scopes (``jax.named_scope``) reach a profile as a stat of an
event's metadata, which ``trace.Reduced.ops`` does not keep. The library
keeps the other half: ``horovod_tpu.core.telemetry.program()`` is the
optimized step program as ``{instruction name: (opcode, op_name scope path,
umbrella, custom-call target, fused parts)}``, and an ``XLA Ops`` event is
named after the instruction it ran (``%fusion.4749 = ...``:
``trace._OP_LABEL``'s first group), names being unique in a module. So the
join is by name. ``while``, ``conditional`` and ``call`` events are umbrellas
over their body's leaf events and are dropped; what is left runs one at a
time, so the classes sum to the busy time.

Interval rules are ``trace.py``'s (device 0, ``XLA Ops``, the reducer's
window, a union of intervals a class); the phase of an operation is
``phases.phase_of`` of its ``op_name``. A join that cannot be trusted exits,
as ``spans.aligned`` does: a reader over a partial join would be worse than
none.
"""

import collections

from chipbench import phases, trace

UNKNOWN_SHARE = 1e-3    # of the busy time, in operations the map lacks
SUM_ERROR = 1e-3        # the classes' sum against busy_s / steps
MOSAIC = "tpu_custom_call"
# a model's own scopes; an operation counts under the innermost one
MODEL_SCOPES = (
    "hvd_mla", "hvd_mtp", "hvd_moe_route", "hvd_moe_dispatch",
    "hvd_moe_experts", "hvd_moe_shared", "hvd_moe_combine", "hvd_attn_full",
    "hvd_attn_window", "hvd_loop_pass", "hvd_loop_head", "hvd_exit_gate")


def available():
    """Whether the library under test keeps its program's map (the parent
    of the PR that added it does not: the readers then apply to no cell)."""
    from horovod_tpu.core import telemetry
    return hasattr(telemetry, "program")


def family(cell, *families):
    """A cell of one of ``families``, run by a library that has the map."""
    return cell["config"].get("family") in families and available()


def innermost(op_name):
    """The model scope an operation lies in most deeply, or None."""
    best, at = None, -1
    for scope in MODEL_SCOPES:
        i = op_name.rfind(scope)
        if i > at:
            best, at = scope, i
    return best


# Device milliseconds a step: ``phase_ms`` by ``phases.phase_of`` (every
# phase present), ``scope_ms`` by innermost model scope without the Mosaic
# kernels (0.0 for a scope no operation lies in), ``grouped_ms`` in Mosaic
# kernels the library did not name; ``facts`` is the info line's.
Joined = collections.namedtuple("Joined", "phase_ms scope_ms grouped_ms facts")


def join(reduced, instructions):
    if not any("hvd_forward" in i.op_name for i in instructions.values()):
        raise SystemExit(
            "chipbench: the step program's map holds no 'hvd_forward' scope "
            f"in {len(instructions)} instructions (a program served from a "
            "compile cache that was filled before the scopes existed?): "
            "nothing can be read by scope")
    by_phase = {phase: [] for phase, _ in phases.PHASES}
    by_phase[phases.OTHER] = []
    by_scope = collections.defaultdict(list)
    grouped, unknown, unknown_names = [], [], set()
    across = {"ns": 0, "by_root": collections.Counter(),
              "by_bytes": collections.Counter()}
    shares = {}     # fusion name -> its parts' bytes by phase, made once
    for name, start, end in reduced.ops:
        m = trace._OP_LABEL.match(name)
        inst = instructions.get(m.group(1)) if m else None
        if inst is None:
            unknown.append((start, end))
            unknown_names.add(name[:60])
        elif not inst.umbrella:
            phase = phases.phase_of(inst.op_name)
            by_phase[phase].append((start, end))
            if inst.target != MOSAIC:
                by_scope[innermost(inst.op_name)].append((start, end))
            elif not inst.name.startswith("hvd_"):
                grouped.append((start, end))
            if inst.parts:
                if inst.name not in shares:
                    shares[inst.name] = _bytes_by_phase(inst)
                _across_phases(across, shares[inst.name], phase, end - start)
    steps = reduced.steps

    def ms(spans):
        return trace.total(trace.merge(spans)) / 1e6 / steps

    busy_ms = 1e3 * reduced.busy_s / steps
    unknown_share = ms(unknown) / busy_ms
    if unknown_share > UNKNOWN_SHARE:
        raise SystemExit(
            f"chipbench: {100 * unknown_share:.3f}% of the window's busy time "
            f"is in {len(unknown_names)} operations the step program's map "
            f"does not know (such as {sorted(unknown_names)[:3]})")
    phase_ms = {phase: ms(spans) for phase, spans in by_phase.items()}
    if abs(sum(phase_ms.values()) - busy_ms) > SUM_ERROR * busy_ms:
        raise SystemExit(
            f"chipbench: the phases sum to {sum(phase_ms.values()):.4f} ms a "
            f"step and the device was busy {busy_ms:.4f}: {phase_ms}")
    return Joined(
        phase_ms, {scope: ms(by_scope[scope]) for scope in MODEL_SCOPES},
        ms(grouped),
        {"instructions": len(instructions),
         "joined_share": 1 - unknown_share,
         "fused_across_phases_ms": across["ns"] / 1e6 / steps,
         "across_by_root_ms": {k: v / 1e6 / steps
                               for k, v in across["by_root"].items()},
         "across_by_bytes_ms": {k: v / 1e6 / steps
                                for k, v in across["by_bytes"].items()}})


def _bytes_by_phase(inst):
    per = collections.Counter()
    for op_name, nbytes in inst.parts:
        phase = phases.phase_of(op_name)
        if phase != phases.OTHER:
            per[phase] += nbytes
    return per


def _across_phases(across, per, root_phase, ns):
    """A fusion whose parts lie in more than one phase: its time under its
    root's phase (how a profile, and ``phase_ms``, read it) beside the same
    time split by the bytes its parts of each phase write."""
    if len(per) > 1:
        across["ns"] += ns
        across["by_root"][root_phase] += ns
        whole = sum(per.values())
        for phase, nbytes in per.items():
            across["by_bytes"][phase] += ns * (nbytes / whole if whole
                                               else 1 / len(per))


def of(run):
    """The run's join, made once (after the window, the traced steps and
    the reference check: no step waits for it) and said on an info line."""
    joined = getattr(run, "scopes", None)
    if joined is None:
        from horovod_tpu.core import telemetry
        program = telemetry.program("train_step")
        joined = run.scopes = join(run.trace, program.instructions)
        run.info(what="program_map", map_seconds=program.map_seconds,
                 compiled=program.compiled, **joined.facts)
    return joined
