"""The library's own spans as the per-layer readers see them.

``horovod_tpu.core.telemetry.span`` keeps ``(name, seq, t0_ns, t1_ns,
thread_id)`` in memory, on ``time.perf_counter_ns`` (the harness's clock), and
the readers run in the process that ran the loop, so they read the log
directly. ``seq`` is the ordinal of the batch (``input.*``) or of the call
(``step.dispatch``). The harness makes two blocking calls, ``WARM_STEPS`` warm
steps, then the window, and hands readers the window's own outside timers
(``run.waits``, ``run.dispatches``); each ``next(feed)`` is one ``input.wait``
and each ``step(...)`` one ``step.dispatch``, so the window's step ``j`` has
``seq`` ``2 + WARM_STEPS + j``. A reader that cannot show that alignment exits.
"""

import statistics

from chipbench import harness, trace

CLOCK_ERROR_S = 0.05e-3     # an inner span may read this much over its outer timer
MISALIGNED_SHARE = 0.01     # ... in at most this share of the window's steps


def available():
    """Whether the library under test keeps spans (the parent of the PR that
    added them does not: its readers then apply to no cell)."""
    from horovod_tpu.core import telemetry
    return hasattr(telemetry, "spans")


def log(name):
    """Spans ``name``, oldest first. A full log has dropped its oldest
    spans, so nothing read from it could be trusted."""
    from horovod_tpu.core import telemetry
    everything = telemetry.spans()
    if len(everything) >= telemetry.SPAN_LOG:
        raise SystemExit(f"chipbench: the span log is full "
                         f"({len(everything)} spans): its oldest are gone")
    return [s for s in everything if s[0] == name]


def first_seq():
    return 2 + harness.WARM_STEPS


def window(run, name):
    """The window's spans ``name`` in step order: exactly one for each
    ``seq`` the window's steps have."""
    by_seq = {}
    for s in log(name):
        by_seq.setdefault(s[1], []).append(s)
    picked = []
    for seq in range(first_seq(), first_seq() + len(run.waits)):
        if len(by_seq.get(seq, ())) != 1:
            raise SystemExit(
                f"chipbench: {len(by_seq.get(seq, ()))} {name!r} spans with "
                f"seq {seq}; the window's {len(run.waits)} steps need one each")
        picked.append(by_seq[seq][0])
    return picked


def seconds(spans):
    return [(s[3] - s[2]) / 1e9 for s in spans]


def aligned(name, inner, outer, outer_name):
    """``inner`` (a span's seconds, step by step) sits inside the timer the
    harness keeps round the same step, ``outer``: it cannot be longer."""
    over = [j for j, (i, o) in enumerate(zip(inner, outer))
            if i > o + CLOCK_ERROR_S]
    if len(over) > MISALIGNED_SHARE * len(inner):
        j = over[0]
        raise SystemExit(
            f"chipbench: {name!r} is not aligned with {outer_name}: in "
            f"{len(over)} of {len(inner)} window steps the span is longer than "
            f"the timer round it (first at window step {j}: span "
            f"{1e3 * inner[j]:.4f} ms, {outer_name} {1e3 * outer[j]:.4f} ms)")
    return inner


def median_ms(values):
    return 1e3 * statistics.median(values)


def before_window(names):
    """Seconds covered by spans ``names`` that end before the window's first
    ``step.dispatch`` starts, as a union: JAX reports a nested trace inside
    the trace that called it."""
    opening = [s for s in log("step.dispatch") if s[1] == first_seq()]
    if len(opening) != 1:
        raise SystemExit(f"chipbench: {len(opening)} 'step.dispatch' spans "
                         f"with seq {first_seq()}: no window to end set-up at")
    return covered(names, 0, opening[0][2])


def covered(names, lo, hi):
    """Seconds of ``[lo, hi]`` (clock nanoseconds) that whole spans
    ``names`` inside it cover."""
    return trace.total(trace.merge(
        (s[2], s[3]) for name in names for s in log(name)
        if s[2] >= lo and s[3] <= hi)) / 1e9
