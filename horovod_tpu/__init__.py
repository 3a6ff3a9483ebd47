"""horovod_tpu — a TPU-native distributed training framework with the
capabilities of Horovod (reference: rb-determined-ai/horovod).

The familiar surface — ``init / rank / size / allreduce / allgather /
broadcast / alltoall / reducescatter / grouped ops / process sets /
Compression / Adasum / DistributedOptimizer / elastic / horovodrun`` —
rebuilt idiomatically on JAX/XLA: collectives compile into the XLA graph
over ICI/DCN meshes instead of routing through a host-side background
thread + NCCL (see SURVEY.md for the full mapping).

Two ways to use the collectives:

- **In-graph** (the hot path): call ``hvd.allreduce(...)`` & friends inside
  your own ``shard_map``/``pjit`` over a mesh whose rank axis is
  ``hvd.RANK_AXIS`` (or pass ``axis_name=``). This is where the reference
  needed 2,000 lines of negotiation and a fusion buffer; here it is one HLO.
- **Eager** (``hvd.eager.*``): per-rank semantics from plain Python over the
  global mesh, for startup broadcast, tools and parity tests.
"""

import os as _os

import jax as _jax


def _place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a home every entry point
    shares. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and
    nothing is touched. Unset: ``<checkout>/.jax_cache`` — a fixed path,
    because a directory that moves between runs never hits."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    checkout = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    _jax.config.update("jax_compilation_cache_dir",
                       _os.path.join(checkout, ".jax_cache"))


_place_compile_cache()

from . import collectives, core
from .collectives import (Adasum, Average, Compression, Max, Min, Product,
                          Sum, adasum_allreduce, allgather, allgather_v,
                          allreduce, alltoall, alltoall_v, barrier, broadcast,
                          eager, grouped_allgather, grouped_allreduce,
                          grouped_broadcast, grouped_reducescatter,
                          hierarchical_adasum, hierarchical_allreduce,
                          iterate_with_join, join,
                          join_allreduce, join_count, reducescatter)
from .core import (Config, HorovodInternalError, HostsUpdatedInterrupt,
                   ProcessSet, RANK_AXIS, add_process_set, cuda_built,
                   global_process_set, cross_rank,
                   cross_size, gloo_enabled, init, is_homogeneous,
                   is_initialized, local_rank, local_size, mesh, mpi_enabled, mpi_threads_supported,
                   nccl_built, rank, remove_process_set, rocm_built, shutdown,
                   size, start_timeline, stop_timeline, xla_built)

__version__ = "0.1.0"


def __getattr__(name):
    # Lazy submodule access (horovod_tpu.optimizer, .elastic, .models, ...)
    # so importing the top level stays light.
    import importlib
    if name in ("optimizer", "elastic", "models", "parallel", "runner",
                "tools", "ops", "utils", "train", "callbacks", "checkpoint",
                "data", "ray", "spark", "torch"):
        try:
            return importlib.import_module(f".{name}", __name__)
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise  # a real missing dependency inside the submodule
            raise AttributeError(
                f"module 'horovod_tpu' has no attribute {name!r}") from e
    raise AttributeError(f"module 'horovod_tpu' has no attribute {name!r}")
