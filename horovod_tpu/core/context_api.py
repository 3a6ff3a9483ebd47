"""Global context: device mesh, rank/size queries, init/shutdown.

Reference parity: ``horovod/common/operations.cc`` (``horovod_init``,
``horovod_rank/size/local_rank/...``) + ``horovod/common/basics.py``
(SURVEY.md §3.1). The reference's init spawns a background coordination
thread and negotiates communicators over MPI/Gloo; under SPMD/XLA there is no
negotiation to do — ``init()`` here (a) optionally joins the multi-host
coordination service (``jax.distributed.initialize`` over DCN — the analog of
the reference's Gloo HTTP rendezvous), (b) builds a 1-D ``jax.sharding.Mesh``
over all devices whose axis is the Horovod "rank" axis, and (c) loads the
``HOROVOD_*`` config.

Rank model: the reference runs one process per GPU, so rank == device. JAX is
single-controller (one process drives many devices), so "rank" is a
*device-level* concept:

- ``size()``       → total devices in the mesh (== reference world size)
- ``local_size()`` → devices addressable by this process
- ``rank()``       → inside ``shard_map``/``pmap`` tracing: the per-device
                     axis index (a traced value). On the host: the global
                     index of this process's first device.
- ``local_rank()`` → inside tracing: ``rank() % local_size``; host: 0.
- ``cross_size()/cross_rank()`` → process (host) count / index, matching the
  reference's cross-communicator used for hierarchical ops.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from .config import Config
from .exceptions import NotInitializedError
from .logging import get_logger
from . import telemetry as _telemetry
from .process_sets import ProcessSet, ProcessSetTable

#: Name of the mesh axis that plays the role of the Horovod rank axis.
RANK_AXIS = "hvd"


class Context:
    """Singleton holding the mesh, config and process-set table."""

    def __init__(self, mesh: Mesh, config: Config, axis_name: str = RANK_AXIS):
        self.mesh = mesh
        self.config = config
        self.axis_name = axis_name
        self.process_sets = ProcessSetTable(mesh.devices.size)
        self.timeline = None  # attached by tools.timeline when enabled

    @property
    def size(self) -> int:
        return self.mesh.devices.size


_context: Optional[Context] = None
_lock = threading.Lock()


def init(devices: Optional[Sequence[jax.Device]] = None,
         axis_name: str = RANK_AXIS,
         coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None,
         config: Optional[Config] = None,
         mesh: Optional[Mesh] = None) -> Context:
    """Initialise the global context. Idempotent, like the reference's
    ``InitializeHorovodOnce`` (operations.cc).

    Multi-host: if ``coordinator_address`` is given (or the launcher exported
    ``HOROVOD_COORDINATOR_ADDR``), joins the JAX coordination service first —
    the TPU analog of the reference's rendezvous (SURVEY.md §2.7).

    ``mesh``: optionally a prebuilt (possibly multi-axis) Mesh — e.g. from
    ``parallel.mesh.create_hybrid_mesh`` — instead of the default 1-D mesh
    over all devices. With a multi-axis mesh the rank axis becomes the TUPLE
    of its axis names (outer axes ride DCN, innermost rides ICI) and
    ``HOROVOD_HIERARCHICAL_ALLREDUCE=1`` makes every default allreduce take
    the two-level reducescatter→cross-psum→allgather path (collectives/ops.py
    ``hierarchical_allreduce``), matching the reference's hierarchical NCCL
    pipeline (nccl_operations.cc, SURVEY §2.2).
    """
    global _context
    with _lock:
        if _context is not None:
            return _context
        _telemetry.install_compile_listener()
        coord = coordinator_address or os.environ.get("HOROVOD_COORDINATOR_ADDR")
        # NOTE: jax.distributed.initialize must run before ANY call that
        # initialises the XLA backend (incl. jax.process_count/jax.devices),
        # so the guard must not touch the backend.
        if coord and not jax.distributed.is_initialized():
            nproc = num_processes or int(os.environ.get("HOROVOD_NUM_PROCESSES", "0")) or None
            pid = process_id if process_id is not None else (
                int(os.environ["HOROVOD_PROCESS_ID"])
                if "HOROVOD_PROCESS_ID" in os.environ else None)
            get_logger().info("joining coordination service at %s", coord)
            # --start-timeout (launcher) bounds the rendezvous here, on the
            # worker side, where "all peers joined" is actually observable.
            kw = {}
            if "HOROVOD_START_TIMEOUT" in os.environ:
                try:
                    kw["initialization_timeout"] = int(
                        float(os.environ["HOROVOD_START_TIMEOUT"]))
                except (TypeError, ValueError):
                    pass
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=nproc,
                process_id=pid, **kw)
        cfg = config or Config.from_env()
        if "HOROVOD_FUSION_THRESHOLD" in os.environ:
            # Forward the fusion threshold to XLA's collective combiner —
            # OPT-IN via HOROVOD_FUSION_APPLY_XLA_FLAGS=1: XLA aborts the
            # process (F-level, uncatchable) on any flag name its build
            # does not know, and the combiner flag names vary by backend/
            # version (both backends of this image reject them — measured;
            # in-graph fusion via grouped ops is the default mechanism,
            # docs/tensor-fusion.md).
            if os.environ.get("HOROVOD_FUSION_APPLY_XLA_FLAGS", "") in (
                    "1", "true", "yes", "on"):
                flags = os.environ.get("XLA_FLAGS", "")
                add = [f for f in cfg.xla_combiner_flags()
                       if f not in flags]
                if add:
                    os.environ["XLA_FLAGS"] = (
                        flags + " " + " ".join(add)).strip()
                    get_logger().info(
                        "forwarded HOROVOD_FUSION_THRESHOLD=%d to XLA "
                        "combiner flags (effective only if the XLA backend "
                        "was not yet initialized)",
                        cfg.fusion_threshold_bytes)
            else:
                get_logger().info(
                    "HOROVOD_FUSION_THRESHOLD=%d recorded (gradient fusion "
                    "is in-graph via grouped ops; set "
                    "HOROVOD_FUSION_APPLY_XLA_FLAGS=1 to also emit XLA "
                    "combiner flags if your XLA build supports them)",
                    cfg.fusion_threshold_bytes)
        timeline = None
        if cfg.timeline_path:
            from ..tools.timeline import Timeline
            timeline = Timeline(cfg.timeline_path,
                                mark_cycles=cfg.timeline_mark_cycles)
            timeline.marker("INIT")
            # Close (flush events + the closing bracket) even when the
            # script never calls shutdown() — the reference's timeline is
            # usable after abnormal exits for the same reason.
            import atexit
            atexit.register(timeline.close)
        if mesh is not None:
            if devices is not None:
                raise ValueError("pass either devices or mesh, not both")
            devs = list(mesh.devices.flat)
            if len(mesh.axis_names) > 1:
                axis_name = tuple(mesh.axis_names)
            else:
                axis_name = mesh.axis_names[0]
        else:
            devs = list(devices) if devices is not None else jax.devices()
            if (cfg.hierarchical_allreduce and devices is None
                    and jax.process_count() > 1):
                # Reference parity: HOROVOD_HIERARCHICAL_ALLREDUCE needs no
                # topology input from the user — node boundaries are known.
                # Here the analog is the process boundary: build a
                # (cross=process over DCN) x (intra=local devices over ICI)
                # mesh automatically when the world is homogeneous, so the
                # env var alone reshapes the gradient exchange.
                by_proc: dict = {}
                for d in devs:
                    by_proc.setdefault(d.process_index, []).append(d)
                counts = {len(v) for v in by_proc.values()}
                if len(by_proc) == jax.process_count() and len(counts) == 1:
                    names = (f"{axis_name}_cross", f"{axis_name}_intra")
                    mesh = Mesh(
                        np.asarray([by_proc[p] for p in sorted(by_proc)]),
                        names)
                    axis_name = names
                    get_logger().info(
                        "hierarchical allreduce: auto mesh %s over %d "
                        "process(es) x %d local device(s)", names,
                        len(by_proc), counts.pop())
                else:
                    get_logger().warning(
                        "HOROVOD_HIERARCHICAL_ALLREDUCE=1 ignored: process "
                        "topology is not homogeneous (per-process device "
                        "counts %s) — using a flat 1-D mesh",
                        {p: len(v) for p, v in sorted(by_proc.items())})
            if mesh is None:
                mesh = Mesh(np.asarray(devs), (axis_name,))
        ctx = Context(mesh, cfg, axis_name)
        ctx.timeline = timeline
        get_logger().info(
            "initialized: %d device(s), %d process(es), platform=%s",
            len(devs), jax.process_count(), devs[0].platform)
        _context = ctx
        return _context


def shutdown() -> None:
    """Tear down the context (reference: ``horovod_shutdown`` tears down
    every frontend)."""
    global _context, _process_engine
    with _lock:
        if _context is not None and _context.timeline is not None:
            _context.timeline.close()
        _context = None
        # context_api OWNS the shared engine's lifecycle: shut it down
        # before dropping the reference (the frontends below only release
        # their own _state and must not tear down an engine they share).
        if _process_engine is not None:
            _process_engine.shutdown()
        _process_engine = None
    # The torch/TF runtimes cache the shared engine; letting them keep a
    # pre-shutdown instance while the next lazy caller creates a fresh one
    # would reintroduce the two-engines-one-coordination-service hazard
    # process_engine() exists to prevent. Tear them down too (only if the
    # binding module was actually imported — no import side effects here).
    import sys as _sys
    for mod in ("horovod_tpu.torch.mpi_ops",
                "horovod_tpu.tensorflow.mpi_ops"):
        m = _sys.modules.get(mod)
        if m is not None:
            m.shutdown()


_process_engine = None


def process_engine():
    """Shared host-side process-collective engine for the JAX path's object
    helpers (``allgather_object``/``broadcast_object``, elastic state
    sync): the same transport the torch/TF bindings ride
    (``default_engine`` — JaxProcessEngine on multi-host pods), so those
    helpers inherit the engine's mismatch protocol AND the transport stall
    watchdog instead of blocking forever in raw ``multihost_utils`` calls
    against a dead peer (VERDICT r4 #1). Lazy; cleared by ``shutdown``."""
    global _process_engine
    with _lock:
        if _process_engine is None:
            from .engine import default_engine
            _process_engine = default_engine()
        return _process_engine


def is_initialized() -> bool:
    return _context is not None


def context() -> Context:
    if _context is None:
        raise NotInitializedError()
    return _context


def mesh() -> Mesh:
    return context().mesh


def _in_trace(axis_name: str) -> bool:
    try:
        jax.lax.axis_size(axis_name)
        return True
    except NameError:
        return False


def size() -> int:
    """World size == device count (one rank per device, as in the reference)."""
    return context().size


def local_size() -> int:
    return jax.local_device_count()


def rank():
    """Per-device rank inside traced code; first-local-device rank on host."""
    ctx = context()
    if _in_trace(ctx.axis_name):
        return jax.lax.axis_index(ctx.axis_name)
    local = [d for d in ctx.mesh.devices.flat
             if d.process_index == jax.process_index()]
    if not local:
        return 0
    flat = list(ctx.mesh.devices.flat)
    return flat.index(local[0])


def local_rank():
    ctx = context()
    if _in_trace(ctx.axis_name):
        return jax.lax.axis_index(ctx.axis_name) % jax.local_device_count()
    return 0


def cross_size() -> int:
    return jax.process_count()


def cross_rank() -> int:
    return jax.process_index()


def is_homogeneous() -> bool:
    """True when every process drives the same number of devices."""
    return size() == cross_size() * local_size()


# Build-introspection parity with basics.py (nccl_built/mpi_enabled/...):
# on TPU the only data plane is XLA collectives, always built.
def xla_built() -> bool:
    return True


def mpi_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def cuda_built() -> bool:
    """Reference basics.py probe set: no CUDA/ROCm in the TPU build."""
    return False


def rocm_built() -> bool:
    return False


def add_process_set(ranks: Sequence[int]) -> ProcessSet:
    return context().process_sets.add(ranks)


def remove_process_set(ps: "ProcessSet | int") -> None:
    context().process_sets.remove(ps)


def global_process_set() -> ProcessSet:
    """The id-0 set over all ranks (parity: ``hvd.global_process_set``,
    common/process_sets.py — there a module attribute, here a function since
    world size is only known after ``init()``)."""
    return context().process_sets.global_set


def mpi_threads_supported() -> bool:
    """Parity: ``hvd.mpi_threads_supported()`` (basics.py). Always False —
    there is no MPI in this build; scripts probing it fall back correctly."""
    return False


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Begin writing the host-side Chrome-trace timeline to ``file_path``.

    Parity: ``hvd.start_timeline`` (basics.py → timeline.cc ActivityStart
    plumbing). Device-side activity is better captured by jax.profiler; use
    ``tools.merge_chrome_traces`` to combine both views."""
    from ..tools.timeline import Timeline
    ctx = context()
    if ctx.timeline is not None:
        ctx.timeline.close()
    ctx.timeline = Timeline(file_path, mark_cycles=mark_cycles)


def stop_timeline() -> None:
    """Parity: ``hvd.stop_timeline`` — flush and close the timeline."""
    ctx = context()
    if ctx.timeline is not None:
        ctx.timeline.close()
        ctx.timeline = None
