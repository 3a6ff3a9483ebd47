"""Device time a step under Kimi delta attention's scopes (``hvd_kda``, and
``hvd_kda_scan`` inside it: the chunked delta rule, forward and backward),
read from the traced window joined with the step program's own map as
``scopes.py`` joins them.

``scopes.MODEL_SCOPES`` counts an operation under its innermost model scope;
these two are not among them, so an operation of a delta layer keeps its
place there (no model scope) and is counted here as well: under every one of
these scopes its ``op_name`` holds. The operations of the scan's loop body
are leaves, and count; the loop's own event is an umbrella, and does not.
Mosaic kernels and umbrellas are left out, as there."""

from chipbench import scopes, trace

SCOPES = ("hvd_kda", "hvd_kda_scan")


def of(run):
    """``{scope: ms a step}``, made once a run after ``scopes.of``'s checks
    on the join (a join that cannot be trusted exits there)."""
    joined = getattr(run, "kda_scopes", None)
    if joined is None:
        from horovod_tpu.core import telemetry
        scopes.of(run)
        instructions = telemetry.program("train_step").instructions
        spans = {scope: [] for scope in SCOPES}
        for name, start, end in run.trace.ops:
            m = trace._OP_LABEL.match(name)
            inst = instructions.get(m.group(1)) if m else None
            if inst is None or inst.umbrella or inst.target == scopes.MOSAIC:
                continue
            for scope in SCOPES:
                if scope in inst.op_name:
                    spans[scope].append((start, end))
        joined = run.kda_scopes = {
            scope: trace.total(trace.merge(spans[scope])) / 1e6
            / run.trace.steps for scope in SCOPES}
    return joined


def applies(cell):
    """A cell of the delta-attention family, run by a library that keeps its
    program's map."""
    return scopes.family(cell, "delta_moe")
