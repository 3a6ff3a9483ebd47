"""Host seconds of set-up inside JAX's tracing and lowering: the library's
``compile.trace`` and ``compile.lower`` spans (JAX's own
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` events) that
end before the window opens, as a union (a nested trace lies inside the trace
that called it). An earlier line says how much of the first ``step(...)``
call tracing, lowering and the backend explain."""
from chipbench import spans

UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
_NAMES = ("compile.trace", "compile.lower")


def applies(cell):
    return spans.available()


def read(run):
    first = [s for s in spans.log("step.dispatch") if s[1] == 0]
    if len(first) == 1:
        lo, hi = first[0][2], first[0][3]
        parts = {"trace_lower_s": spans.covered(_NAMES, lo, hi),
                 "backend_s": spans.covered(("compile.backend",), lo, hi)}
        run.info(what="first_step_split", first_step_s=run.first_step_s,
                 dispatch_s=(hi - lo) / 1e9, **parts,
                 explained_share=sum(parts.values()) / run.first_step_s)
    return spans.before_window(_NAMES)
