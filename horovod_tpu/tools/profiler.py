"""Device-side profiling — the XLA half of the observability story.

Reference parity: the reference's timeline (timeline.cc) records the full
per-tensor lifecycle because all phases happen on the host thread it owns.
Here the device-side phases (collective execution, fusion, overlap) live in
XLA's own trace. ``trace`` wraps ``jax.profiler`` so one context manager
captures a TensorBoard/Perfetto-loadable xplane trace alongside the
host-side Chrome trace from ``tools/timeline.py`` (HOROVOD_TIMELINE); load
both into Perfetto to see the merged picture, or use ``annotate`` to inject
named host spans into the xplane trace itself.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_trace: bool = False) -> Iterator[None]:
    """Capture a device trace: ``with profiler.trace("/tmp/trace"): step()``.
    View with TensorBoard's profile plugin or Perfetto."""
    jax.profiler.start_trace(logdir,
                             create_perfetto_trace=create_perfetto_trace)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named span that shows up inside the device trace (TraceAnnotation).
    Usable as decorator or context manager around host code issuing work."""
    return jax.profiler.TraceAnnotation(name)

