"""Observability & tuning tools (SURVEY.md §5.1/§5.2/§2.1 parity).

- :class:`Timeline` — host-side Chrome-trace writer (HOROVOD_TIMELINE).
- :mod:`profiler` — device-side xplane traces (jax.profiler wrappers).
- :mod:`perf` — step-time budgets from xplane traces of one's own job,
  the ratchet over a history of such records, and regression diffs
  (``python -m horovod_tpu.tools.perf`` — docs/profiling.md).
- :class:`StallInspector` — step-progress watchdog (HOROVOD_STALL_CHECK_*).
- :class:`MismatchDetector` — debug cross-process collective-signature
  check (HOROVOD_MISMATCH_CHECK).
- :class:`Autotuner` — GP/EI Bayesian autotuner for combiner/microbatch
  knobs (HOROVOD_AUTOTUNE_LOG), reference parameter_manager parity.
"""

from . import perf, profiler
from .autotune import (Autotuner, CatDim, Dim, GaussianProcess, IntDim,
                       LogIntDim, StepAutotuner, expected_improvement)
from .mismatch import MismatchDetector, MismatchError, detector, maybe_record
from .stall import StallInspector
from .timeline import Timeline, merge_chrome_traces

__all__ = ["Autotuner", "CatDim", "Dim", "GaussianProcess", "IntDim", "StepAutotuner",
           "LogIntDim", "MismatchDetector", "MismatchError",
           "StallInspector", "Timeline", "detector",
           "expected_improvement", "maybe_record", "merge_chrome_traces",
           "perf", "profiler"]
