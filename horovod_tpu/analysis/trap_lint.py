"""AST trap lint: mechanically enforce CLAUDE.md's environment traps.

Pure ``ast`` analysis — nothing is imported or executed, so linting a
script can never trigger the traps it looks for.  Each check encodes a
failure mode that cost real debugging time on this codebase (see
CLAUDE.md "Environment traps"):

- ``lint-xla-flags`` (ERROR): mutation of ``os.environ["XLA_FLAGS"]``
  outside the ``HOROVOD_FUSION_APPLY_XLA_FLAGS`` opt-in guard with flags
  beyond the known-safe set.  XLA **F-aborts the process** on unknown
  flag names, and both backends here reject the collective-combiner
  flags.
- ``lint-torch-seed`` (WARNING): ``torch.manual_seed`` inside a nested
  function — the thread-sim rank-fn pattern, where concurrent rank
  threads race torch's GLOBAL RNG.  Top-level calls (before ranks fork)
  are fine.
- ``lint-slope-cadence`` (WARNING): a bench file builds a stepped arm
  with ``deferred_pair(..., every=k)`` but passes ``slope_time_paired``
  window lengths that are not multiples of ``k`` — min-over-repeats then
  cherry-picks the cheap phase of the cadence.
- ``lint-silent-rpc`` (WARNING): an RPC client ``try`` block (one that
  calls ``urlopen``) whose ``except OSError``-family handler is nothing
  but ``return None``/``return False`` — the swallow pattern that made a
  dead coordinator indistinguishable from "no change" and silently
  disabled every rescue layer built on the control plane.  Retry/escalate
  (elastic/service.py's retrying client), or mark a deliberate residual
  with the pragma.
- ``jax-unguarded-apply`` (WARNING): a train-step function that both
  computes gradients (``value_and_grad``/``grad``) and applies them
  (``optax.apply_updates``) with no finiteness guard in sight (no
  ``isfinite`` / ``grads_finite`` / ``health_vector`` / sentinel
  reference).  One NaN micro-batch then poisons the parameters forever —
  and under data parallelism the allreduce spreads it to EVERY replica
  in one step.  Guard with ``core/sentinel.py``'s health vector (or an
  explicit ``jnp.isfinite`` check), or pragma deliberate throwaway
  loops.
- ``lint-unbounded-poll`` (WARNING): a ``while`` loop that calls the
  coordinator's ``get_world`` with no pacing anywhere in the loop body —
  no ``sleep``, no ``wait``/``wait_for``, and no ``wait=`` long-poll
  bound on the call itself.  One such loop is a busy-wait against a
  single HTTP service; N of them is the thundering herd the pod-scale
  protocol exists to prevent (benchmarks/control_plane.py measures the
  melt).  Pace with an interval + jitter
  (``HOROVOD_ELASTIC_POLL_JITTER``), or park server-side with
  ``get_world(wait=...)``.  Bounded ``for`` loops are exempt.
- ``lint-monolithic-psum`` (WARNING): a gradient-computing train step
  that reduces its grads leaf-by-leaf via ``tree_map(lambda g:
  lax.psum(g, ...), grads)`` — one collective per leaf, in pytree
  (first-layer-first) order.  The grouped/fused path
  (``collectives.ops.grouped_allreduce``) packs leaves into
  reverse-layer buckets sized by ``HOROVOD_FUSION_THRESHOLD`` so the
  allreduce overlaps the backward; per-leaf psums forfeit both the
  fusion and the overlap (docs/fusion.md).
- ``lint-blocking-telemetry`` (WARNING): a telemetry record call
  (``telemetry.inc/set_gauge/observe/record_event`` or a
  registry/ring method) inside a loop whose arguments force a device
  fetch — ``.block_until_ready()``, ``np.asarray(...)``,
  ``jax.device_get(...)``.  Telemetry's overhead contract
  (docs/telemetry.md) is host-side-only recording: a blocking fetch
  per step stalls the async dispatch pipeline, exactly the cost the
  ≤1.02 overhead guard exists to prevent.  Record values the host
  already fetched (the watchdog span / Keras logs), or fetch OUTSIDE
  the telemetry call at a point that must synchronize anyway.

- ``lint-recompile-in-request-path`` (WARNING): a serve loop — one that
  drains requests from a queue/socket — feeding a jitted callable
  directly with request-shaped inputs, with no padding/bucketing call
  anywhere in the loop.  jit caches compiled programs BY SHAPE, so every
  distinct request/batch size compiles a fresh program on the request
  path (seconds of latency, unbounded compile cache).  Coalesce into a
  fixed set of bucket sizes with padding
  (``serving/server.py::pad_to_bucket``, ``HOROVOD_SERVING_BUCKETS``) so
  compiles are bounded by configuration, not traffic — docs/serving.md.

- ``lint-blocking-commit`` (WARNING): a bare ``jax.device_get`` inside
  a step/commit loop — a loop that also calls ``.commit()``.  The
  elastic commit path is pipelined (elastic/state.py
  ``_CommitWriter``): ``commit()`` takes a cheap on-device copy and the
  background writer overlaps the device→host transfer with subsequent
  steps, so a synchronous ``device_get`` of training state feeding the
  commit re-serializes exactly the stall the async writer removes (and
  shows up as ``hvd_commit_stall_seconds``).  Hand ``commit()`` the
  LIVE arrays and let the writer fetch them off-thread; fetch host
  copies yourself only outside the step loop.

- ``lint-xplane-umbrella`` (WARNING): an xplane walk that accumulates
  ``ev.duration_ps`` over a ``.events`` line with no umbrella filtering
  in sight.  Two traps hide here (CLAUDE.md): ``%while``/``tuple.``/
  ``jit_`` events are scan/module *umbrellas* whose spans cover their
  leaf children — summing them double counts the step; and the "Async
  XLA Ops" line carries overlapped DMA *windows*, not occupancy — adding
  it to device-busy time invents throughput.  Route xplane parsing
  through the vetted parsers (``horovod_tpu.tools.perf``,
  ``chipbench/trace.py``), filter on ``UMBRELLA_PREFIXES``, or
  pragma a span-sum that is deliberately a wall/overlap figure.

- ``lint-decode-host-sync`` (WARNING): a host loop that drives a decode
  step (any call whose name mentions ``decode``) AND forces a device
  fetch in the same loop body — ``block_until_ready``, ``np.asarray``,
  ``jax.device_get``, or ``common.sync``.  Continuous decode lives on
  async dispatch: the engine enqueues one fixed-shape program per step
  and the host races ahead admitting/retiring slots, so ONE blocking
  fetch per iteration re-serializes the pipeline and tokens/s collapses
  to round-trip latency (the decode arms in benchmarks/serving.py sync
  once AFTER the timed window for exactly this reason).  Read tokens
  from the engine's device-side buffer and fetch outside the loop;
  pragma deliberate per-step probes (latency measurement, numerics
  parity tests).

- ``lint-replicated-kv-pool`` (WARNING): a function that both builds a
  device mesh (``Mesh``/``create_mesh``/``make_mesh``/...) and allocates
  paged-KV pools (``init_kv_pools``) without ever placing the pool names
  onto the mesh (no ``device_put``/``make_array_from_callback``/
  ``with_sharding_constraint`` sees them).  jit then defaults the pools
  to REPLICATED: every device holds the full ``[L, blocks, bs, heads,
  hd]`` cache — tp× the KV memory the head-sharded layout needs — and
  the shard_map'd decode program reshards them every step.  Place with
  ``jax.device_put(pool, NamedSharding(mesh, kv_pool_spec()))`` (the
  engine additionally pins ``Format(Layout(...))`` at the KV gather
  seams — serving/decode.py, docs/serving.md "Sharded decode"), or
  pragma a deliberately replicated single-device pool.

- ``lint-accum-psum-order`` (WARNING): a ``lax.scan``/``lax.fori_loop``
  body that both computes gradients (``value_and_grad``/``grad``) and
  reduces them across the mesh (``psum``/``pmean``) — the microbatch
  accumulation loop reducing INSIDE the loop body.  With
  ``accum_steps=n`` that is n collectives per step instead of one: n×
  the wire bytes for a mathematically identical result (psum is linear,
  so summing locally and reducing once after the loop commutes).
  Accumulate on-replica and let the single post-loop update carry the
  one allreduce — ``train/step_builder.py::accumulate_gradients`` is
  the reference shape.

- ``lint-host-draft-loop`` (WARNING): a speculative-decode DRAFTING
  loop (its target, iterable, or a called name mentions ``draft``) that
  invokes a jitted callable or a ``decode``/``verify``/``prefill``
  device program per candidate token.  Speculation's contract
  (docs/serving.md "Speculative decode") is host-side drafting over
  tokens the engine already holds and ONE K-wide verify call per tick —
  a device round-trip per drafted token serializes exactly the
  memory-bound pipeline speculation exists to widen, costing more than
  the plain path it replaces.  Draft from host ints
  (``serving/decode.py::_ngram_draft``), batch the window, verify once;
  pragma a deliberate draft-model forward.

- ``lint-rank-conditional-collective`` (ERROR): a collective call
  (``allreduce``/``broadcast``/``psum``/``barrier``/...) lexically
  inside the body of an ``if`` whose test calls ``rank()`` /
  ``local_rank()`` / ``cross_rank()`` — the oldest Horovod failure
  class of all: only some ranks reach the collective, the rest never
  show up, and the job hangs with no error.  This is the host-level AST
  complement to the jaxpr engine's per-rank stream diffing
  (``analysis.jaxpr.analyze_rank_divergence``): the AST rule catches
  the pattern in ANY Python file without tracing; the jaxpr check
  proves it on the traced step.  Rank-conditional host work (rank-0
  logging, checkpoint writes) is fine — only collective NAMES inside
  the branch trip this.  A deliberate both-paths protocol (e.g. the
  engine's ``broadcast_object`` early-return, where both branches call
  the same collective) carries the pragma.

- ``lint-unverified-peer-blob`` (WARNING): a function that receives
  bytes from a peer (binds the result of a ``.read()``/``.recv()`` on a
  network path — the function also calls ``urlopen``/``recv``) and
  writes those SAME bytes into the content-addressed store with
  ``put_blob`` while showing no digest-verification evidence anywhere in
  the function (no ``blob_digest``/``check``/``compare_digest`` call, no
  ``verify`` name, no ``BlobIntegrityError`` reference).  The store
  content-addresses what it is GIVEN — ``put_blob`` on corrupt peer
  bytes mints a valid-looking blob under the corrupt bytes' own digest,
  and the corruption is only discovered when a LATER reader compares
  against the manifest digest (or never, if the bad digest is then
  recorded).  Verify at the fetch seam instead: re-hash the body against
  the requested digest and raise ``BlobIntegrityError`` on mismatch so
  the fetcher re-elects a source
  (``elastic/blobmesh.py::BlobPeerClient.fetch``,
  docs/checkpointing.md "Peer-sourced resume").

- ``lint-unbounded-admission`` (WARNING): an HTTP request handler
  (``do_GET``/``do_POST``/``do_PUT`` on a class deriving from a
  ``*HTTPRequestHandler``) enqueues work — ``.put``/``.put_nowait`` on a
  queue-ish receiver, or any ``*enqueue*`` call — while neither the
  method nor its class shows any shed evidence (a ``qsize``/``full``
  check, a comparison against a ``*max*``/``*cap*`` bound, a 429
  constant, or a ``shed``/``admit`` name).  An unbounded admission queue
  turns a traffic spike into unbounded latency for EVERY queued request,
  then timeout storms and retry amplification; bound the queue and shed
  past the bound with 429 + ``Retry-After`` so clients back off instead
  of piling on (``serving/server.py::InferenceServer._admit``,
  docs/fleet.md "Overload containment").

- ``lint-heavy-signal-handler`` (WARNING): a handler registered with
  ``signal.signal`` whose body performs blocking work — an RPC
  (``urlopen``/``requests.*``), a device fetch
  (``block_until_ready``/``device_get``), or a file write (``open``/
  ``.write``/``fsync``/``json.dump``).  Signal handlers run at an
  arbitrary bytecode boundary INSIDE whatever the main thread was doing:
  re-entering an HTTP client mid-request deadlocks it, a device fetch
  can re-enter the runtime under its own lock, and buffered I/O is not
  reentrant (CPython may raise, or interleave corrupted output).  The
  vetted pattern is ``core/lifecycle.py``: the handler only sets a flag
  and ``os.write``s one byte to a nonblocking self-pipe (the only
  async-signal-safe write), and a watcher thread does everything heavy
  outside signal context.  ``os.write``/``os.kill``/``signal.signal``
  are exempt (they ARE the safe vocabulary); ``SIG_IGN``/``SIG_DFL``
  dispositions never trip this.

Suppress any finding by putting ``# hvd-analyze: ok`` on the flagged
line.
"""

import ast
import os
from typing import Iterable, List, Optional, Sequence

from .findings import Finding, Severity

SUPPRESS_PRAGMA = "hvd-analyze: ok"

# XLA flags that are safe on both backends in this image (the CPU
# device-count fake used by the whole test tier).
SAFE_XLA_FLAGS = frozenset({"--xla_force_host_platform_device_count"})

XLA_GUARD_ENV = "HOROVOD_FUSION_APPLY_XLA_FLAGS"

# OSError-family exception names whose silent-return handlers around an
# RPC call hide control-plane loss (lint-silent-rpc).
RPC_SWALLOW_EXCEPTIONS = frozenset({
    "OSError", "IOError", "ConnectionError", "TimeoutError",
    "URLError", "HTTPError",
})

# jax-unguarded-apply vocabulary: gradient producers, update appliers,
# and the tokens whose presence counts as a finiteness guard.
GRAD_CALL_NAMES = frozenset({"value_and_grad", "grad"})
APPLY_CALL_NAMES = frozenset({"apply_updates"})
GUARD_TOKENS = frozenset({
    "isfinite", "grads_finite", "health_vector", "all_finite",
})

# lint-monolithic-psum vocabulary: the per-leaf mesh reductions whose
# tree-mapped form forfeits the fused/bucketed collective path.
LEAF_REDUCE_NAMES = frozenset({"psum", "pmean"})

# lint-accum-psum-order vocabulary: the loop combinators whose body is a
# candidate microbatch accumulation loop (positional index of the body
# callable in each call's args).
ACCUM_LOOP_BODY_ARG = {"scan": 0, "fori_loop": 2}

# lint-replicated-kv-pool vocabulary: the paged-KV pool allocator, the
# mesh builders whose presence marks a function as multi-device, and the
# placement calls that count as sharding the allocated pools.
KV_POOL_ALLOC_NAMES = frozenset({"init_kv_pools"})
MESH_BUILD_NAMES = frozenset({
    "Mesh", "create_mesh", "create_hybrid_mesh", "make_mesh",
    "create_device_mesh", "create_hybrid_device_mesh",
})
KV_PLACEMENT_NAMES = frozenset({
    "device_put", "make_array_from_callback", "with_sharding_constraint",
})

# lint-unbounded-poll vocabulary: the coordinator poll, and the calls
# that count as pacing a poll loop (a sleep, a condition/event wait, or
# the server-side long-poll park via get_world(wait=...)).
POLL_CALL_NAMES = frozenset({"get_world"})
PACING_CALL_NAMES = frozenset({"sleep", "wait", "wait_for"})

# lint-blocking-telemetry vocabulary: record entry points (generic names
# like ``inc`` count only with a telemetry/registry/ring prefix; the
# distinctive ones also count bare, as imported from core.telemetry),
# and the calls that force a device fetch.
TELEMETRY_RECORD_NAMES = frozenset({
    "inc", "set_gauge", "observe", "record_event", "record",
})
TELEMETRY_BARE_NAMES = frozenset({"record_event", "set_gauge"})
FETCH_CALL_NAMES = frozenset({"block_until_ready", "asarray",
                              "device_get"})

# lint-decode-host-sync vocabulary: the fetches that serialize a decode
# loop. ``sync`` is benchmarks/common.py's device->host fetch — it counts
# here (a decode loop syncing per step defeats async dispatch) even
# though it is not a jax API name.
DECODE_FETCH_NAMES = frozenset({"block_until_ready", "asarray",
                                "device_get", "sync"})


def _is_decode_fetch(name: str) -> bool:
    """``asarray`` counts only as numpy's (np./numpy./bare): jnp.asarray
    is host->device and never blocks on device results."""
    parts = name.split(".")
    if parts[-1] not in DECODE_FETCH_NAMES:
        return False
    if parts[-1] == "asarray":
        prefix = ".".join(parts[:-1]).lower()
        return "jnp" not in prefix and "jax" not in prefix
    return True


# lint-host-draft-loop vocabulary: the call-name fragments that mark a
# call inside a drafting loop as a per-token device program (jit-bound
# names from the file's prescan count too).
DRAFT_DEVICE_CALL_TOKENS = ("decode", "verify", "prefill")


def _mentions_draft(node) -> bool:
    """True when a subtree names anything draft-ish — the loop-header /
    called-name evidence that a loop iterates per drafted candidate."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "draft" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "draft" in sub.attr.lower():
            return True
    return False

# lint-blocking-commit vocabulary: the commit entry point marking a loop
# as a step/commit loop, and the synchronous fetch that defeats the async
# commit writer. Restricted to ``device_get`` (not ``asarray``, which has
# many host-side uses) to keep the rule precise.
COMMIT_CALL_NAMES = frozenset({"commit"})
COMMIT_FETCH_NAMES = frozenset({"device_get"})

# lint-unverified-peer-blob vocabulary: the network receive whose result
# is peer-provided bytes, the receive binding that names them, the store
# write that must only ever see verified bytes, and the calls/names that
# count as digest-verification evidence.
PEER_NET_CALL_NAMES = frozenset({"urlopen", "recv", "recvfrom"})
PEER_RECV_BIND_NAMES = frozenset({"read", "recv", "recvfrom"})
BLOB_WRITE_NAMES = frozenset({"put_blob"})
BLOB_VERIFY_NAMES = frozenset({"blob_digest", "check", "compare_digest"})


def _is_blob_verify_evidence(node) -> bool:
    """True when a subtree shows digest-verification awareness: a verify
    vocabulary call, any name/attr mentioning 'verify', or a reference to
    BlobIntegrityError (the raise-on-mismatch pattern)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) \
                and _dotted(sub.func).split(".")[-1] in BLOB_VERIFY_NAMES:
            return True
        tok = sub.attr if isinstance(sub, ast.Attribute) else (
            sub.id if isinstance(sub, ast.Name) else None)
        if tok is not None and ("verify" in tok.lower()
                                or tok == "BlobIntegrityError"):
            return True
    return False

# lint-recompile-in-request-path vocabulary: calls that mark a loop as
# draining requests (distinctive names count bare; the generic ``get``
# needs a queue-ish receiver so dict.get stays clean), and the
# pad/bucket call names whose presence marks the loop as batching.
REQUEST_DRAIN_NAMES = frozenset({"get_nowait", "recv", "recv_json",
                                 "accept"})
REQUEST_DRAIN_GENERIC = frozenset({"get"})
REQUEST_RECEIVER_TOKENS = ("queue", "request", "req", "inbox", "pending")


# lint-unbounded-admission vocabulary: the handler methods that admit
# traffic, the enqueue spellings (``put``/``put_nowait`` need a queue-ish
# receiver so dict/env puts stay clean; ``*enqueue*`` counts bare), and
# the tokens that count as shed/bounding evidence.
ADMISSION_HANDLER_METHODS = frozenset({"do_GET", "do_POST", "do_PUT"})
ADMISSION_ENQUEUE_NAMES = frozenset({"put", "put_nowait"})
ADMISSION_RECEIVER_TOKENS = ("queue", "pending", "inbox", "backlog",
                             "work", "req")
ADMISSION_EVIDENCE_EXACT = frozenset({"qsize", "full"})


def _admission_shed_evidence(node) -> bool:
    """True when a subtree shows bounded-admission awareness: a queue
    depth/capacity probe, a 429 constant, a shed/admit name, or a
    comparison against a ``*max*``/``*cap*`` bound."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and sub.value == 429:
            return True
        tok = sub.attr if isinstance(sub, ast.Attribute) else (
            sub.id if isinstance(sub, ast.Name) else None)
        if tok is not None:
            t = tok.lower()
            if t in ADMISSION_EVIDENCE_EXACT or "shed" in t or "admit" in t:
                return True
        if isinstance(sub, ast.Compare):
            for side in [sub.left] + list(sub.comparators):
                for n in ast.walk(side):
                    st = n.attr if isinstance(n, ast.Attribute) else (
                        n.id if isinstance(n, ast.Name) else None)
                    if st is not None and ("max" in st.lower()
                                           or "cap" in st.lower()):
                        return True
    return False


# lint-heavy-signal-handler vocabulary: the blocking calls that must
# never run in signal context, by class. ``write`` counts only as a
# METHOD (dotted) and never on the os module — ``os.write`` to a
# nonblocking self-pipe is the one async-signal-safe write and exactly
# what the vetted handler (core/lifecycle.py) does.
HANDLER_RPC_NAMES = frozenset({"urlopen"})
HANDLER_FETCH_NAMES = frozenset({"block_until_ready", "device_get"})
HANDLER_WRITE_NAMES = frozenset({"open", "fsync", "dump"})
HANDLER_DISPOSITIONS = frozenset({"SIG_IGN", "SIG_DFL"})


def _heavy_handler_call_kind(name: str) -> Optional[str]:
    """Classify a dotted call name as handler-unsafe, or None."""
    parts = name.split(".")
    last = parts[-1]
    prefix = ".".join(parts[:-1])
    if last in HANDLER_RPC_NAMES or parts[0] == "requests":
        return "RPC"
    if last in HANDLER_FETCH_NAMES:
        return "device fetch"
    if last in HANDLER_WRITE_NAMES:
        return "file write"
    if last == "write" and prefix and prefix != "os":
        return "file write"
    return None


# lint-xplane-umbrella vocabulary: the umbrella prefixes whose presence
# as string constants counts as filtering evidence (mirrors
# tools/perf.py UMBRELLA_PREFIXES — kept literal here so the lint stays
# import-free), plus the attribute accumulated.
XPLANE_UMBRELLA_STRINGS = frozenset({"while", "tuple.", "jit_"})
XPLANE_DURATION_ATTR = "duration_ps"


def _xplane_filter_evidence(node) -> bool:
    """True when a subtree shows awareness of the umbrella trap: an
    umbrella-prefix string constant, or any name/attribute mentioning
    'umbrella' (the shared ``UMBRELLA_PREFIXES`` table)."""
    for sub in ast.walk(node):
        s = _const_str(sub)
        if s is not None and s in XPLANE_UMBRELLA_STRINGS:
            return True
        tok = sub.attr if isinstance(sub, ast.Attribute) else (
            sub.id if isinstance(sub, ast.Name) else None)
        if tok is not None and "umbrella" in tok.lower():
            return True
    return False


def _iters_events(node) -> bool:
    name = _dotted(node)
    return name == "events" or name.endswith(".events")


def _has_duration_attr(node) -> bool:
    return any(isinstance(sub, ast.Attribute)
               and sub.attr == XPLANE_DURATION_ATTR
               for sub in ast.walk(node))


def _is_request_drain(name: str) -> bool:
    parts = name.split(".")
    if parts[-1] in REQUEST_DRAIN_NAMES:
        return True
    if parts[-1] in REQUEST_DRAIN_GENERIC:
        prefix = ".".join(parts[:-1]).lower()
        return any(t in prefix for t in REQUEST_RECEIVER_TOKENS)
    return False


def _is_batching_call(name: str) -> bool:
    last = name.split(".")[-1].lower()
    return "pad" in last or "bucket" in last


def _is_telemetry_record(name: str) -> bool:
    parts = name.split(".")
    if parts[-1] not in TELEMETRY_RECORD_NAMES:
        return False
    prefix = ".".join(parts[:-1]).lower()
    if not prefix:
        return parts[-1] in TELEMETRY_BARE_NAMES
    return ("telemetry" in prefix or prefix.endswith("registry")
            or prefix.endswith("ring"))


def _is_guard_token(tok: str) -> bool:
    return tok in GUARD_TOKENS or "sentinel" in tok.lower()


def _is_tree_map(name: str) -> bool:
    """jax.tree_util.tree_map / jax.tree.map / bare tree_map."""
    return name.split(".")[-1] == "tree_map" or name.endswith("tree.map")


def _maps_leafwise_reduce(fn_arg) -> bool:
    """True when a tree_map's function argument reduces each leaf over a
    mesh axis: a lambda whose body calls psum/pmean, a direct psum/pmean
    reference, or a functools.partial over one."""
    if isinstance(fn_arg, ast.Lambda):
        return any(
            isinstance(sub, ast.Call)
            and _dotted(sub.func).split(".")[-1] in LEAF_REDUCE_NAMES
            for sub in ast.walk(fn_arg.body))
    if isinstance(fn_arg, (ast.Attribute, ast.Name)):
        return _dotted(fn_arg).split(".")[-1] in LEAF_REDUCE_NAMES
    if isinstance(fn_arg, ast.Call) \
            and _dotted(fn_arg.func).split(".")[-1] == "partial" \
            and fn_arg.args:
        return _dotted(fn_arg.args[0]).split(".")[-1] in LEAF_REDUCE_NAMES
    return False


# lint-rank-conditional-collective vocabulary: the rank accessors whose
# presence as a CALL in an if-test marks the branch rank-divergent, and
# the collective entry points (host engine API + jax primitives) that
# must never sit inside such a branch.
RANK_CALL_NAMES = frozenset({"rank", "local_rank", "cross_rank"})
RANK_CONDITIONAL_COLLECTIVES = frozenset({
    "allreduce", "grouped_allreduce", "hierarchical_allreduce",
    "allgather", "allgather_object", "broadcast", "broadcast_object",
    "alltoall", "reducescatter", "barrier",
    "psum", "pmean", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "reduce_scatter", "all_to_all",
})


# Directory names never linted (fixture corpora are known-bad on purpose).
EXCLUDED_DIR_NAMES = frozenset({
    "analysis_fixtures", "__pycache__", ".git", "node_modules",
})


def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _is_os_environ(node) -> bool:
    """Matches ``os.environ`` or a bare ``environ`` name."""
    if isinstance(node, ast.Attribute) and node.attr == "environ":
        return True
    if isinstance(node, ast.Name) and node.id == "environ":
        return True
    return False


def _dotted(node) -> str:
    """Best-effort dotted name of a call target (``torch.manual_seed``)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _Lint(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.lines = source.splitlines()
        self.findings: List[Finding] = []
        self._func_depth = 0
        self._xla_guard_depth = 0
        # jax-unguarded-apply: apply_updates call nodes already attributed
        # to an inner (gradient-computing) function — enclosing functions
        # must not re-flag them.
        self._apply_handled: set = set()
        # lint-monolithic-psum: same innermost-first attribution for
        # tree-mapped per-leaf psum sites.
        self._monolithic_handled: set = set()
        # lint-replicated-kv-pool: pool-allocating assigns already
        # attributed to an inner (mesh-building) function.
        self._kv_pool_handled: set = set()
        # lint-unbounded-poll: poll sites already attributed to an
        # enclosing while loop (nested loops must not re-flag them).
        self._poll_handled: set = set()
        # lint-blocking-commit: fetch sites already attributed to an
        # enclosing (outermost) commit loop.
        self._commit_fetch_handled: set = set()
        # lint-decode-host-sync: fetch sites already attributed to an
        # enclosing (outermost) decode loop.
        self._decode_fetch_handled: set = set()
        # lint-host-draft-loop: device-call sites already attributed to
        # an enclosing (outermost) drafting loop.
        self._draft_loop_handled: set = set()
        # lint-recompile-in-request-path: names bound to jit(...) results
        # in this file (prescanned in visit_Module), and jit call sites
        # already attributed to an enclosing serve loop.
        self._jit_names: set = set()
        self._recompile_handled: set = set()
        # lint-accum-psum-order: function defs by name (prescanned, so a
        # scan body passed as a named function resolves regardless of
        # definition order), and reduce sites already flagged.
        self._funcdefs: dict = {}
        self._accum_handled: set = set()
        # lint-blocking-telemetry: loop nesting (a "step loop" is any
        # for/while the record call sits inside).
        self._loop_depth = 0
        # lint-xplane-umbrella: duration accumulations already attributed
        # to an enclosing events loop (nested walks must not re-flag).
        self._xplane_handled: set = set()
        # lint-rank-conditional-collective: collective call sites already
        # attributed to an enclosing (outermost) rank-conditional.
        self._rank_cond_handled: set = set()
        # lint-unverified-peer-blob: put_blob sites already attributed to
        # the smallest enclosing recv-and-store function.
        self._peer_blob_handled: set = set()
        # lint-slope-cadence state
        self.cadences: List[int] = []           # every=k constants
        self.slope_windows: List = []           # (line, [window ints])

    # -- helpers -------------------------------------------------------

    def _suppressed(self, node) -> bool:
        line = getattr(node, "lineno", 0)
        if 0 < line <= len(self.lines):
            return SUPPRESS_PRAGMA in self.lines[line - 1]
        return False

    def _add(self, check_id, severity, node, message, detail=None):
        if not self._suppressed(node):
            self.findings.append(Finding(
                check_id, severity, self.path,
                getattr(node, "lineno", 0), message, detail))

    def _statement_flags(self, node) -> List[str]:
        """All ``--flag_name`` tokens in string constants under node."""
        flags = []
        for sub in ast.walk(node):
            s = _const_str(sub)
            if s:
                for tok in s.split():
                    if tok.startswith("--"):
                        flags.append(tok.split("=", 1)[0])
        return flags

    def _check_environ_store(self, key_node, stmt, value_nodes):
        key = _const_str(key_node)
        if key == "XLA_FLAGS":
            if self._xla_guard_depth > 0:
                return  # inside the documented opt-in guard
            flags = [f for v in value_nodes for f in self._statement_flags(v)]
            unsafe = [f for f in flags if f not in SAFE_XLA_FLAGS]
            if unsafe or not flags:
                self._add(
                    "lint-xla-flags", Severity.ERROR, stmt,
                    f"XLA_FLAGS mutated outside the {XLA_GUARD_ENV} "
                    f"opt-in guard"
                    + (f" with non-allowlisted flags {unsafe}" if unsafe
                       else " with flags not statically known")
                    + "; XLA F-aborts the process on unknown flag names",
                    {"flags": flags})

    # -- visitors ------------------------------------------------------

    def visit_Module(self, node):
        # Prescan for jit-bound names (assignment order vs use order is
        # irrelevant to the serve-loop check, so collect them all first):
        # ``f = jax.jit(...)`` / ``f = jit(...)`` and ``@jax.jit`` defs.
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call) \
                    and _dotted(sub.value.func).split(".")[-1] == "jit":
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Name):
                        self._jit_names.add(tgt.id)
            elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._funcdefs.setdefault(sub.name, sub)
                for dec in sub.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    if _dotted(d).split(".")[-1] == "jit":
                        self._jit_names.add(sub.name)
        self.generic_visit(node)

    def _check_rank_conditional_collective(self, node):
        """lint-rank-conditional-collective: a collective call lexically
        under an ``if rank() ...`` branch — the deadlock class the
        reference controller's negotiation existed to surface.  Outer If
        visited first, so nested rank-conditionals skip already-claimed
        call sites.  Only the branch bodies are scanned; a rank call
        ALONE (logging, checkpoint gating) never trips this."""
        test_is_ranked = any(
            isinstance(sub, ast.Call)
            and _dotted(sub.func).split(".")[-1] in RANK_CALL_NAMES
            for sub in ast.walk(node.test))
        if not test_is_ranked:
            return
        for stmt in list(node.body) + list(node.orelse):
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                name = _dotted(sub.func)
                if name.split(".")[-1] not in RANK_CONDITIONAL_COLLECTIVES:
                    continue
                if id(sub) in self._rank_cond_handled:
                    continue
                self._rank_cond_handled.add(id(sub))
                self._add(
                    "lint-rank-conditional-collective", Severity.ERROR,
                    sub,
                    f"collective {name!r} inside a rank-conditional "
                    f"branch (if ...rank()... at line {node.lineno}): "
                    "only some ranks reach the collective and the rest "
                    "never show up — the job hangs with no error (the "
                    "mismatch class horovod/common/controller.cc "
                    "negotiates at runtime). Hoist the collective out "
                    "of the branch so EVERY rank calls it, gate only "
                    "the host-side work on rank, or pragma a vetted "
                    "both-paths protocol (docs/analysis.md)",
                    {"conditional_line": node.lineno})

    def visit_If(self, node):
        self._check_rank_conditional_collective(node)
        guarded = any(
            isinstance(sub, ast.Constant) and sub.value == XLA_GUARD_ENV
            for sub in ast.walk(node.test))
        if guarded:
            self._xla_guard_depth += 1
            for child in node.body:
                self.visit(child)
            self._xla_guard_depth -= 1
            for child in node.orelse:
                self.visit(child)
        else:
            self.generic_visit(node)

    def visit_Assign(self, node):
        for tgt in node.targets:
            if isinstance(tgt, ast.Subscript) and _is_os_environ(tgt.value):
                key = tgt.slice
                if isinstance(key, ast.Index):  # py<3.9 AST, defensive
                    key = key.value
                self._check_environ_store(key, node, [node.value])
        self.generic_visit(node)

    def visit_Call(self, node):
        name = _dotted(node.func)

        # os.environ.setdefault("XLA_FLAGS", ...) / .update({...})
        if isinstance(node.func, ast.Attribute) \
                and _is_os_environ(node.func.value):
            if node.func.attr == "setdefault" and node.args:
                self._check_environ_store(
                    node.args[0], node, node.args[1:2])
            elif node.func.attr == "update":
                for arg in node.args:
                    if isinstance(arg, ast.Dict):
                        for k, v in zip(arg.keys, arg.values):
                            if k is not None:
                                self._check_environ_store(k, node, [v])

        if name.endswith("manual_seed") and name.startswith("torch"):
            if self._func_depth >= 2:
                self._add(
                    "lint-torch-seed", Severity.WARNING, node,
                    "torch.manual_seed inside a nested function (rank-fn "
                    "pattern): thread-sim ranks race torch's global RNG — "
                    "seed once before forking ranks, or init weights "
                    "deterministically without it")

        if name.endswith("deferred_pair"):
            for kw in node.keywords:
                if kw.arg == "every" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, int):
                    self.cadences.append(kw.value.value)

        self._check_accum_psum_order(node, name)
        self._check_heavy_signal_handler(node, name)

        if self._loop_depth > 0 and _is_telemetry_record(name):
            fetches = [
                _dotted(sub.func).split(".")[-1]
                for arg in (list(node.args)
                            + [kw.value for kw in node.keywords])
                for sub in ast.walk(arg)
                if isinstance(sub, ast.Call)
                and _dotted(sub.func).split(".")[-1] in FETCH_CALL_NAMES]
            if fetches:
                self._add(
                    "lint-blocking-telemetry", Severity.WARNING, node,
                    f"telemetry record call forces a device fetch "
                    f"({'/'.join(sorted(set(fetches)))}) inside a loop: "
                    "per-step blocking reads stall the async dispatch "
                    "pipeline — record values the host already fetched "
                    "(watchdog span, Keras logs), or fetch outside the "
                    "telemetry call at a point that must synchronize "
                    "anyway (docs/telemetry.md overhead contract)",
                    {"fetches": fetches})

        # lint-xplane-umbrella (genexp form): sum(ev.duration_ps for ev
        # in line.events) with no umbrella-filter evidence inside the
        # comprehension — counts scan/module umbrella spans (and the
        # Async-ops overlap windows) as occupancy.
        if name == "sum" and node.args \
                and isinstance(node.args[0], ast.GeneratorExp):
            gen = node.args[0]
            if gen.generators and _iters_events(gen.generators[0].iter) \
                    and _has_duration_attr(gen) \
                    and not _xplane_filter_evidence(gen):
                self._add(
                    "lint-xplane-umbrella", Severity.WARNING, node,
                    "xplane duration_ps summed over a raw .events line "
                    "with no umbrella filtering: %while/tuple./jit_ "
                    "events are scan/module umbrellas covering their "
                    "children (double counts the step), and 'Async XLA "
                    "Ops' spans are overlap windows, not occupancy — "
                    "use the vetted parsers (horovod_tpu.tools.perf, "
                    "chipbench/trace.py), filter on UMBRELLA_PREFIXES, or "
                    "pragma a deliberate wall/overlap sum")

        if name.endswith("slope_time_paired"):
            windows = []
            for arg in node.args[1:3]:
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, int):
                    windows.append(arg.value)
            for kw in node.keywords:
                if kw.arg in ("s_short", "s_long") \
                        and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, int):
                    windows.append(kw.value.value)
            if windows:
                self.slope_windows.append((node, windows))

        self.generic_visit(node)

    def _resolve_handler_body(self, arg):
        """Resolve a signal-handler argument to walkable statements: a
        Lambda inline, a Name or ``self._method`` Attribute via the
        module prescan (``_funcdefs`` holds methods too — ast.walk).
        None for SIG_IGN/SIG_DFL dispositions and unresolvable refs."""
        if isinstance(arg, ast.Lambda):
            return [arg.body]
        if isinstance(arg, (ast.Name, ast.Attribute)):
            last = _dotted(arg).split(".")[-1]
            if last in HANDLER_DISPOSITIONS:
                return None
            fn = self._funcdefs.get(last)
            if fn is not None:
                return list(fn.body)
        return None

    def _check_heavy_signal_handler(self, node, name):
        """lint-heavy-signal-handler: blocking work lexically inside a
        ``signal.signal``-registered handler body.  One finding per
        registration, anchored at the registration call (the handler
        function may be registered from several places with different
        vetting)."""
        parts = name.split(".")
        if parts[-1] != "signal" or len(node.args) < 2:
            return
        prefix = ".".join(parts[:-1])
        if prefix and "signal" not in prefix.lower():
            return  # some other object's .signal() method
        body = self._resolve_handler_body(node.args[1])
        if body is None:
            return
        heavy = []
        for stmt in body:
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                dotted = _dotted(sub.func)
                kind = _heavy_handler_call_kind(dotted)
                if kind is not None:
                    heavy.append((kind, dotted, sub.lineno))
        if heavy:
            kinds = sorted({k for k, _, _ in heavy})
            self._add(
                "lint-heavy-signal-handler", Severity.WARNING, node,
                f"signal handler does blocking work "
                f"({', '.join(kinds)}: "
                f"{', '.join(sorted({d for _, d, _ in heavy}))}): "
                "handlers run at an arbitrary bytecode boundary inside "
                "whatever the main thread was doing — an RPC re-enters "
                "the HTTP client mid-request, a device fetch can "
                "re-enter the runtime under its own lock, and buffered "
                "file I/O is not reentrant. Set a flag and os.write one "
                "byte to a nonblocking self-pipe, then do the heavy "
                "work on a watcher thread outside signal context "
                "(core/lifecycle.py is the vetted pattern), or pragma "
                "a handler proven to run only on a quiesced process",
                {"calls": [{"kind": k, "call": d, "line": ln}
                           for k, d, ln in heavy]})

    def _check_blocking_commit(self, node):
        """lint-blocking-commit: in a loop that calls ``.commit()``, a
        bare ``jax.device_get`` re-serializes the device→host fetch the
        async commit writer exists to overlap. Visited outer loop first,
        so the whole step loop (not each inner block) gets one pass and
        nested loops skip already-attributed fetch sites."""
        calls = [sub for sub in ast.walk(node) if isinstance(sub, ast.Call)]
        if not any(_dotted(c.func).split(".")[-1] in COMMIT_CALL_NAMES
                   for c in calls):
            return
        for c in calls:
            if _dotted(c.func).split(".")[-1] not in COMMIT_FETCH_NAMES:
                continue
            if id(c) in self._commit_fetch_handled:
                continue
            self._commit_fetch_handled.add(id(c))
            self._add(
                "lint-blocking-commit", Severity.WARNING, c,
                "bare jax.device_get inside a step/commit loop: the "
                "commit path is pipelined (elastic/state.py "
                "_CommitWriter fetches off-thread from a cheap on-device "
                "copy) — a synchronous fetch here re-serializes the "
                "device-to-host stall the async writer removes "
                "(hvd_commit_stall_seconds). Pass commit() the live "
                "arrays; fetch host copies only outside the step loop "
                "(docs/checkpointing.md)")

    def _check_decode_host_sync(self, node):
        """lint-decode-host-sync: a host loop that both drives a decode
        step and forces a device fetch per iteration — the blocking read
        re-serializes the async decode dispatch pipeline (tokens/s
        collapses to round-trip latency). Outer loop visited first;
        nested loops skip already-attributed fetch sites. Comprehensions
        are not loops here on purpose: a list-comp reading a ready host
        buffer is the engine's own retire idiom."""
        calls = [sub for sub in ast.walk(node) if isinstance(sub, ast.Call)]
        if not any("decode" in _dotted(c.func).lower() for c in calls):
            return
        for c in calls:
            if not _is_decode_fetch(_dotted(c.func)):
                continue
            if id(c) in self._decode_fetch_handled:
                continue
            self._decode_fetch_handled.add(id(c))
            self._add(
                "lint-decode-host-sync", Severity.WARNING, c,
                "device fetch inside a decode loop body: continuous "
                "decode lives on async dispatch (one fixed-shape program "
                "per step, host racing ahead on admit/retire), so a "
                "blocking read per iteration serializes the pipeline and "
                "tokens/s collapses to round-trip latency — fetch once "
                "OUTSIDE the loop (benchmarks/serving.py syncs after the "
                "timed window), read tokens from the engine's device-side "
                "buffer, or pragma a deliberate per-step probe "
                "(docs/serving.md)")

    def _check_host_draft_loop(self, node):
        """lint-host-draft-loop: a drafting loop (header or a called
        name mentions ``draft``) that calls a jitted name or a decode/
        verify/prefill program per iteration — a device round-trip per
        candidate token, serializing the pipeline one-shot verification
        exists to widen. Outer loop visited first; nested loops skip
        already-attributed call sites. The drafting evidence and the
        device call must share the loop: a loop that only BUILDS the
        window (host drafting) with the verify call outside stays
        clean — that is the required shape."""
        header = [node.target, node.iter] \
            if isinstance(node, (ast.For, ast.AsyncFor)) else [node.test]
        calls = [sub for sub in ast.walk(node) if isinstance(sub, ast.Call)]
        drafty = any(_mentions_draft(h) for h in header) \
            or any("draft" in _dotted(c.func).lower() for c in calls)
        if not drafty:
            return
        for c in calls:
            dotted = _dotted(c.func)
            last = dotted.split(".")[-1].lower()
            is_device = (
                (isinstance(c.func, ast.Name)
                 and c.func.id in self._jit_names)
                or any(tok in last for tok in DRAFT_DEVICE_CALL_TOKENS))
            if not is_device or id(c) in self._draft_loop_handled:
                continue
            self._draft_loop_handled.add(id(c))
            self._add(
                "lint-host-draft-loop", Severity.WARNING, c,
                f"device program {dotted!r} called inside a per-draft-"
                "token host loop: speculative decode drafts on HOST "
                "tokens the engine already holds and verifies the whole "
                "K-wide window in ONE program call per tick — a device "
                "round-trip per candidate serializes the memory-bound "
                "pipeline speculation exists to widen and costs more "
                "than the plain path (serving/decode.py _ngram_draft, "
                "docs/serving.md 'Speculative decode'); batch the "
                "window and verify once, or pragma a deliberate "
                "draft-model forward")

    def _check_recompile_request_path(self, node):
        """lint-recompile-in-request-path: a request-draining loop calls
        a jit-bound name with no padding/bucketing call anywhere in the
        loop — every distinct request shape compiles a fresh program on
        the serve path. Outer loop visited first; nested loops skip
        already-attributed call sites."""
        calls = [sub for sub in ast.walk(node) if isinstance(sub, ast.Call)]
        if not any(_is_request_drain(_dotted(c.func)) for c in calls):
            return
        if any(_is_batching_call(_dotted(c.func)) for c in calls):
            return
        for c in calls:
            if not (isinstance(c.func, ast.Name)
                    and c.func.id in self._jit_names):
                continue
            if not c.args and not c.keywords:
                continue    # no inputs fed: a thunk relay, not a forward
            if id(c) in self._recompile_handled:
                continue
            self._recompile_handled.add(id(c))
            self._add(
                "lint-recompile-in-request-path", Severity.WARNING, c,
                f"jitted callable {c.func.id!r} fed request-shaped inputs "
                "inside a serve loop with no padding/bucketing: jit "
                "caches programs BY SHAPE, so every distinct batch size "
                "compiles a fresh program on the request path (seconds of "
                "tail latency, unbounded compile cache); coalesce into "
                "fixed buckets with padding (serving/server.py "
                "pad_to_bucket, HOROVOD_SERVING_BUCKETS) so compiles are "
                "bounded by configuration, not traffic — docs/serving.md")

    def _check_xplane_umbrella(self, node):
        """lint-xplane-umbrella (loop form): ``for ev in <line>.events``
        accumulating ``ev.duration_ps`` (AugAssign +=) with no umbrella
        filtering anywhere in the loop. Plain Assigns stay clean so the
        interval-building idiom (``iv = (ev.offset_ps, ...)``) is not
        flagged — intervals feed overlap math, not occupancy."""
        if not _iters_events(node.iter):
            return
        sites = [sub for sub in ast.walk(node)
                 if isinstance(sub, ast.AugAssign)
                 and isinstance(sub.op, ast.Add)
                 and _has_duration_attr(sub.value)
                 and id(sub) not in self._xplane_handled]
        if not sites:
            return
        evidence = _xplane_filter_evidence(node)
        for sub in sites:
            self._xplane_handled.add(id(sub))
            if not evidence:
                self._add(
                    "lint-xplane-umbrella", Severity.WARNING, sub,
                    "xplane duration_ps accumulated over a raw .events "
                    "loop with no umbrella filtering: %while/tuple./jit_ "
                    "events are scan/module umbrellas covering their "
                    "children (double counts the step), and 'Async XLA "
                    "Ops' spans are overlap windows, not occupancy — "
                    "use the vetted parsers (horovod_tpu.tools.perf, "
                    "chipbench/trace.py), filter on UMBRELLA_PREFIXES, or "
                    "pragma a deliberate wall/overlap sum")

    def visit_For(self, node):
        self._check_blocking_commit(node)
        self._check_decode_host_sync(node)
        self._check_host_draft_loop(node)
        self._check_recompile_request_path(node)
        self._check_xplane_umbrella(node)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_AsyncFor = visit_For

    def visit_While(self, node):
        # lint-unbounded-poll: get_world inside a while loop whose body
        # shows no pacing at all — no sleep, no condition/event wait, and
        # no wait= long-poll bound on the call. Bounded for loops (the
        # retry pattern) are exempt; the loop TEST is included in the scan
        # so `while not stop.wait(interval)` counts as paced.
        polls, paced = [], False
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            last = _dotted(sub.func).split(".")[-1]
            if last in POLL_CALL_NAMES:
                if any(kw.arg == "wait" for kw in sub.keywords):
                    paced = True
                elif id(sub) not in self._poll_handled:
                    polls.append(sub)
            elif last in PACING_CALL_NAMES:
                paced = True
        if polls and not paced:
            for call in polls:
                self._poll_handled.add(id(call))
                self._add(
                    "lint-unbounded-poll", Severity.WARNING, call,
                    "get_world called in a while loop with no pacing (no "
                    "sleep/wait in the loop, no wait= long-poll bound on "
                    "the call): a busy-wait against the coordinator — N "
                    "workers doing this is the thundering herd the "
                    "pod-scale protocol prevents; pace with an interval + "
                    "HOROVOD_ELASTIC_POLL_JITTER, or park server-side via "
                    "get_world(wait=...) (see benchmarks/control_plane.py)")
        self._check_blocking_commit(node)
        self._check_decode_host_sync(node)
        self._check_host_draft_loop(node)
        self._check_recompile_request_path(node)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    def visit_Try(self, node):
        # lint-silent-rpc: a try block that performs an RPC (urlopen)
        # whose OSError-family handler just returns None/False — the
        # "dead coordinator == no change" swallow pattern.
        calls_rpc = any(
            isinstance(sub, ast.Call)
            and _dotted(sub.func).split(".")[-1] == "urlopen"
            for stmt in node.body for sub in ast.walk(stmt))
        if calls_rpc:
            for handler in node.handlers:
                names = []
                if handler.type is not None:
                    elts = (handler.type.elts
                            if isinstance(handler.type, ast.Tuple)
                            else [handler.type])
                    names = [_dotted(e).split(".")[-1] for e in elts]
                if not any(n in RPC_SWALLOW_EXCEPTIONS for n in names):
                    continue
                if len(handler.body) == 1 \
                        and isinstance(handler.body[0], ast.Return):
                    val = handler.body[0].value
                    silent = val is None or (
                        isinstance(val, ast.Constant)
                        and val.value in (None, False))
                    if silent:
                        self._add(
                            "lint-silent-rpc", Severity.WARNING, handler,
                            f"except {'/'.join(names)}: return "
                            "None/False swallows an RPC failure — a dead "
                            "peer becomes indistinguishable from 'no "
                            "change' and every layer built on this call "
                            "is silently disabled; retry with backoff "
                            "and escalate on persistent loss instead "
                            "(see elastic/service.py CoordinatorClient)")
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        self._func_depth += 1
        self.generic_visit(node)
        self._func_depth -= 1
        # Runs innermost-first (generic_visit above recursed already), so
        # an apply site is attributed to the SMALLEST enclosing function
        # that also computes gradients — the actual train-step body.
        self._check_unguarded_apply(node)
        self._check_monolithic_psum(node)
        self._check_replicated_kv_pool(node)
        self._check_unverified_peer_blob(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self._check_unbounded_admission(node)
        self.generic_visit(node)

    def _check_unbounded_admission(self, node):
        """lint-unbounded-admission: a request-handler class whose
        do_* methods enqueue work with no shed evidence anywhere in the
        class (a bounding helper method on the same class counts — the
        bound does not have to live inside the handler method)."""
        if not any("HTTPRequestHandler" in _dotted(b) for b in node.bases):
            return
        if _admission_shed_evidence(node):
            return
        for meth in node.body:
            if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if meth.name not in ADMISSION_HANDLER_METHODS:
                continue
            for sub in ast.walk(meth):
                if not isinstance(sub, ast.Call):
                    continue
                dotted = _dotted(sub.func)
                last = dotted.split(".")[-1]
                enqueue = "enqueue" in last.lower() or (
                    last in ADMISSION_ENQUEUE_NAMES
                    and any(tok in dotted.lower()
                            for tok in ADMISSION_RECEIVER_TOKENS))
                if not enqueue:
                    continue
                self._add(
                    "lint-unbounded-admission", Severity.WARNING, sub,
                    f"{meth.name} enqueues work with no queue bound or "
                    "shed path anywhere in the handler class: an "
                    "unbounded admission queue turns a traffic spike "
                    "into unbounded latency for EVERY queued request "
                    "(each waits behind the spike), then timeout storms "
                    "and retry amplification as clients give up and "
                    "resend — check depth against a configured max and "
                    "shed past it with 429 + Retry-After so callers back "
                    "off (serving/server.py::InferenceServer._admit, "
                    "HOROVOD_SERVING_QUEUE_MAX, docs/fleet.md 'Overload "
                    "containment'), or pragma a queue bounded elsewhere",
                    {"call": dotted, "method": meth.name})

    def _check_unverified_peer_blob(self, node):
        """lint-unverified-peer-blob: peer-received bytes landed in the
        blob store without digest verification.  Innermost-first like the
        other function checks: the smallest enclosing function that both
        receives and stores owns the finding."""
        if _is_blob_verify_evidence(node):
            return
        recv_bound, has_net = set(), False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                last = _dotted(sub.func).split(".")[-1]
                if last in PEER_NET_CALL_NAMES:
                    has_net = True
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call) \
                    and (_dotted(sub.value.func).split(".")[-1]
                         in PEER_RECV_BIND_NAMES):
                recv_bound.update(t.id for t in sub.targets
                                  if isinstance(t, ast.Name))
        if not has_net or not recv_bound:
            return
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Call)
                    and _dotted(sub.func).split(".")[-1] in BLOB_WRITE_NAMES
                    and id(sub) not in self._peer_blob_handled):
                continue
            stored = {n.id for arg in sub.args for n in ast.walk(arg)
                      if isinstance(n, ast.Name)}
            if stored & recv_bound:
                self._peer_blob_handled.add(id(sub))
                self._add(
                    "lint-unverified-peer-blob", Severity.WARNING, sub,
                    "bytes received from a peer are written into the "
                    "content-addressed store without digest verification: "
                    "put_blob mints a valid-looking blob under corrupt "
                    "bytes' OWN digest, deferring (or hiding) the "
                    "corruption until a later manifest read — re-hash the "
                    "body against the requested digest at the fetch seam "
                    "and raise BlobIntegrityError on mismatch so the "
                    "fetcher re-elects a source (elastic/blobmesh.py::"
                    "BlobPeerClient.fetch, docs/checkpointing.md "
                    "'Peer-sourced resume'), or pragma a store whose "
                    "caller verifiably hashed the bytes already",
                    {"names": sorted(stored & recv_bound)})

    def _check_replicated_kv_pool(self, node):
        """lint-replicated-kv-pool: KV pools allocated in a function that
        also builds a mesh, with none of the pool names ever passed to a
        placement call — jit defaults them to replicated (full cache per
        device) and the sharded decode program reshards every step.
        Innermost-first like the other function checks: the smallest
        enclosing function that builds the mesh owns the finding."""
        assigns, has_mesh, placed = [], False, set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                last = _dotted(sub.func).split(".")[-1]
                if last in MESH_BUILD_NAMES:
                    has_mesh = True
                elif last in KV_PLACEMENT_NAMES:
                    for arg in (list(sub.args)
                                + [kw.value for kw in sub.keywords]):
                        placed.update(n.id for n in ast.walk(arg)
                                      if isinstance(n, ast.Name))
            elif isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call) \
                    and (_dotted(sub.value.func).split(".")[-1]
                         in KV_POOL_ALLOC_NAMES) \
                    and id(sub) not in self._kv_pool_handled:
                names = []
                for tgt in sub.targets:
                    elts = tgt.elts if isinstance(tgt, (ast.Tuple,
                                                        ast.List)) else [tgt]
                    names.extend(e.id for e in elts
                                 if isinstance(e, ast.Name))
                if names:
                    assigns.append((sub, names))
        if not has_mesh or not assigns:
            return  # single-device pools (no mesh) judged by enclosing scope
        for sub, names in assigns:
            self._kv_pool_handled.add(id(sub))
            if not any(n in placed for n in names):
                self._add(
                    "lint-replicated-kv-pool", Severity.WARNING, sub,
                    "KV pools allocated next to a mesh build but never "
                    "placed on it: jit defaults the pools to REPLICATED, "
                    "so every device holds the full [L, blocks, bs, "
                    "heads, hd] cache (tp× the head-sharded HBM) and the "
                    "shard_map'd decode program reshards it each step — "
                    "place with jax.device_put(pool, NamedSharding(mesh, "
                    "kv_pool_spec())) and pin Format(Layout(...)) at the "
                    "KV gather seams (serving/decode.py, docs/serving.md "
                    "'Sharded decode'), or pragma a deliberately "
                    "replicated single-device pool",
                    {"pools": names})

    def _check_unguarded_apply(self, node):
        """jax-unguarded-apply: gradients computed AND applied in this
        function with no finiteness-guard token anywhere in it."""
        apply_calls, has_grad, has_guard = [], False, False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                last = _dotted(sub.func).split(".")[-1]
                if last in APPLY_CALL_NAMES \
                        and id(sub) not in self._apply_handled:
                    apply_calls.append(sub)
                elif last in GRAD_CALL_NAMES:
                    has_grad = True
            tok = sub.attr if isinstance(sub, ast.Attribute) else (
                sub.id if isinstance(sub, ast.Name) else None)
            if tok is not None and _is_guard_token(tok):
                has_guard = True
        if not apply_calls or not has_grad:
            return  # grads-only or apply-only: judged by enclosing scope
        for call in apply_calls:
            self._apply_handled.add(id(call))
            if not has_guard:
                self._add(
                    "jax-unguarded-apply", Severity.WARNING, call,
                    "optimizer update applied with no finiteness guard in "
                    "a gradient-computing step: one NaN micro-batch "
                    "poisons the parameters forever (and data-parallel "
                    "allreduce spreads it to every replica); guard with "
                    "core/sentinel.py's health_vector or jnp.isfinite, "
                    "or pragma a deliberate throwaway loop")

    def _check_accum_psum_order(self, node, name):
        """lint-accum-psum-order: a scan/fori_loop body that both computes
        gradients and mesh-reduces them — n collectives per step where the
        post-loop update needs only one (psum is linear; reduce AFTER the
        accumulation loop, as in train/step_builder.py's
        accumulate_gradients)."""
        last = name.split(".")[-1]
        body_idx = ACCUM_LOOP_BODY_ARG.get(last)
        if body_idx is None or len(node.args) <= body_idx:
            return
        body = node.args[body_idx]
        if isinstance(body, ast.Name):
            body = self._funcdefs.get(body.id)
        if not isinstance(body, (ast.Lambda, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            return
        sites, has_grad = [], False
        for sub in ast.walk(body):
            if not isinstance(sub, ast.Call):
                continue
            sub_last = _dotted(sub.func).split(".")[-1]
            if sub_last in GRAD_CALL_NAMES:
                has_grad = True
            elif sub_last in LEAF_REDUCE_NAMES \
                    and id(sub) not in self._accum_handled:
                sites.append(sub)
        if not sites or not has_grad:
            return  # reduce-only loops (stat sync) judged elsewhere
        for call in sites:
            self._accum_handled.add(id(call))
            self._add(
                "lint-accum-psum-order", Severity.WARNING, call,
                f"psum/pmean inside a {last} body that also computes "
                "gradients: a microbatch accumulation loop reducing "
                "INSIDE the loop pays one collective per microbatch — "
                "n× the wire bytes of the identical result from "
                "accumulating on-replica and reducing once after the "
                "loop (psum is linear; see "
                "train/step_builder.py::accumulate_gradients)")

    def _check_monolithic_psum(self, node):
        """lint-monolithic-psum: a gradient-computing step reducing its
        grads leaf-by-leaf with a tree-mapped psum/pmean — one collective
        per leaf instead of the grouped/fused bucketed path."""
        sites, has_grad = [], False
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = _dotted(sub.func)
            if name.split(".")[-1] in GRAD_CALL_NAMES:
                has_grad = True
            elif _is_tree_map(name) and sub.args \
                    and id(sub) not in self._monolithic_handled \
                    and _maps_leafwise_reduce(sub.args[0]):
                sites.append(sub)
        if not sites or not has_grad:
            return  # stat-sync tree_maps outside a grad step are fine
        for call in sites:
            self._monolithic_handled.add(id(call))
            self._add(
                "lint-monolithic-psum", Severity.WARNING, call,
                "gradients reduced leaf-by-leaf with a tree-mapped "
                "psum/pmean: one collective per pytree leaf, forfeiting "
                "HOROVOD_FUSION_THRESHOLD bucketing and the backward "
                "overlap it buys; reduce the whole tree through "
                "collectives.ops.grouped_allreduce (or "
                "hierarchical_allreduce) instead — see docs/fusion.md")

    # -- file-level checks ---------------------------------------------

    def finish(self):
        for node, windows in self.slope_windows:
            for k in self.cadences:
                bad = [w for w in windows if w % k != 0]
                if bad:
                    self._add(
                        "lint-slope-cadence", Severity.WARNING, node,
                        f"slope_time_paired windows {windows} are not all "
                        f"multiples of the apply cadence every={k} used in "
                        f"this file; min-over-repeats will cherry-pick the "
                        f"cheap phase of the cadence",
                        {"windows": windows, "every": k})


def lint_source(source: str, path: str = "<string>") -> List[Finding]:
    """Lint one Python source string; returns findings (never executes)."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding("lint-syntax", Severity.ERROR, path,
                        e.lineno or 0, f"cannot parse: {e.msg}")]
    lint = _Lint(path, source)
    lint.visit(tree)
    lint.finish()
    return lint.findings


def iter_python_files(paths: Iterable[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in EXCLUDED_DIR_NAMES]
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
    return out


def lint_paths(paths: Sequence[str]) -> List[Finding]:
    """Lint files/directories (recursively; fixture dirs excluded)."""
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
        except OSError as e:
            findings.append(Finding("lint-io", Severity.ERROR, path, 0,
                                    f"cannot read: {e}"))
            continue
        findings.extend(lint_source(source, path))
    return findings
