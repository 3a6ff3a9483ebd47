"""Continuous-batching decode engine over the paged KV-cache.

Reference analog: none — upstream Horovod served training only (SURVEY.md
§2); this is the serving plane's decode half (docs/serving.md "Decode
path"), built on ``models/decode.py``:

- a **BlockAllocator** hands out fixed-size KV blocks from the
  preallocated device pool (block 0 is reserved as the null block inactive
  slots point at). Free-list discipline: no fragmentation is possible by
  construction — any free block serves any slot, so allocation fails only
  when the pool is genuinely exhausted (asserted by the property tests).
- a fixed-width **slot array**: requests are ADMITTED into free slots at
  prefill (one compile per configured prompt bucket — the same bucketed
  discipline as the ``/predict`` batcher, policed by
  ``lint-recompile-in-request-path``) and RETIRED per decode step; between
  admits the ONE jitted decode program just keeps stepping with an active
  mask, so steady-state decode compiles are zero whatever the traffic does
  (``compile_counts`` is a trace-time counter the guardrail pins).
- the sampled token feeds back as a DEVICE array — the steady-state loop
  never syncs to host (``lint-decode-host-sync``); token values are only
  fetched at retire/refill time.

Weight hot-swap (``HOROVOD_DECODE_SWAP_POLICY``): the engine reads
``registry.current()`` once per step (RCU — one attribute read). On a new
manifest it either

- **refill** (default): re-prefills every live slot's sequence-so-far
  under the new weights into freshly allocated blocks — the block-table
  *remap* path; the refill stall is exactly what the p99
  latency-under-swap rail in ``benchmarks/serving.py`` measures. A live
  sequence that has outgrown the largest prefill bucket is retired early
  with the tokens it has (``truncated`` on the request).
- **drain**: stops admitting, finishes every in-flight slot on the OLD
  weights (the held ``ServedModel`` reference keeps them consistent), and
  adopts the new ones once idle.

**Speculative decode** (``spec_k=`` / ``HOROVOD_DECODE_SPEC_K``,
docs/serving.md "Speculative decode"): with ``K >= 2`` the engine
replaces the single-token decode call with ONE K-wide verify call per
tick (``models/decode.py::make_verify_step``). The K-1 candidate tokens
come from a host-side n-gram / prompt-lookup drafter
(:func:`_ngram_draft`) over tokens the engine already holds — no draft
model, no extra weights, and no extra device round-trips beyond the one
``[S, K]`` fetch acceptance itself requires (drafting is pure host
Python; ``lint-host-draft-loop`` polices the per-draft-token device-call
antipattern). Greedy longest-matching-prefix acceptance emits 1..K
tokens per tick, bit-identical to the non-speculative stream; on
rejection the host simply rewinds ``positions`` to the accepted prefix —
the next verify window starts there and overwrites every rejected
position's K/V before any causal mask can admit it (the paged-pool
rewind invariant, ``tests/test_spec_decode.py``). ``K = 0`` (default)
keeps today's path byte-identical — the verify program is never built
and ``compile_counts`` has no ``verify`` key. All other semantics —
admit/retire/stall/deadlock-break/refill/drain — are unchanged;
``hvd_serving_spec_*`` telemetry reports the accept-length histogram and
draft hit rate.

**Sharded decode** (``mesh=`` / ``HOROVOD_DECODE_TP``, docs/serving.md
"Sharded decode"): the engine runs the tensor-parallel program variants
(``models/decode.py`` ``make_*_tp``) over a ``tp`` mesh axis. ALL host
logic above is mesh-agnostic — block tables, slot state, the allocator,
and the fed-back token array are replicated, so admission/retirement/
swap code is byte-identical; only program construction and array
placement change. The KV pools are head-sharded (``kv_pool_spec``) with
their layout PINNED row-major at the jit boundary (``Format(Layout(...))``
— the r4 DLRM trap: XLA's entry-layout heuristic may otherwise transpose
whole pools around the page gathers), and every params adoption path
funnels through ``_place_params`` so leaves land in their megatron
shardings exactly once (``decode_param_specs``).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..core import telemetry as _telemetry
from ..core.logging import get_logger
from . import constants as SC

FREE = "free"
ACTIVE = "active"


def _ngram_draft(ctx: Sequence[int], n: int, max_ngram: int = 3) -> List[int]:
    """Prompt-lookup drafting: the ``n`` tokens that followed the most
    recent EARLIER occurrence of the longest matching suffix n-gram
    (``max_ngram`` down to 1) anywhere in ``ctx`` (prompt + accepted
    generations). Pure host Python over host ints — by design: the
    verify side is ONE device program call per tick, and a drafter that
    called into the device per candidate token would serialize exactly
    the pipeline speculation exists to widen (``lint-host-draft-loop``).
    Falls back to repeating the last token when nothing matches (a miss
    costs nothing extra: the verify window runs at fixed width K anyway).
    """
    L = len(ctx)
    for m in range(min(max_ngram, L - 1), 0, -1):
        suffix = list(ctx[L - m:])
        for start in range(L - m - 1, -1, -1):
            if list(ctx[start:start + m]) == suffix:
                cont = [int(t) for t in ctx[start + m:start + m + n]]
                if cont:
                    return (cont + [cont[-1]] * n)[:n]
    last = int(ctx[-1]) if L else 0
    return [last] * n


class BlockAllocator:
    """Free-list allocator over KV pool blocks ``1..n_blocks-1`` (block 0
    is the reserved null block)."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.n_blocks = int(n_blocks)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._held = set()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self) -> Optional[int]:
        """One block id, or None when the pool is exhausted."""
        if not self._free:
            return None
        b = self._free.pop()
        self._held.add(b)
        return b

    def alloc_many(self, n: int) -> Optional[List[int]]:
        """``n`` blocks all-or-nothing (admission must not half-allocate)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self._held.update(out)
        return out

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if b not in self._held:
                raise ValueError(f"double free / foreign block {b}")
            self._held.discard(b)
            self._free.append(int(b))


class DecodeRequest:
    """One generation request: submitted, admitted into a slot, completed
    at retire (``event`` fires; ``tokens`` = prompt + generated)."""

    __slots__ = ("prompt", "max_new", "event", "tokens", "error",
                 "truncated", "model_seq", "t0", "ttft_s",
                 "queue_wait_s", "prefill_wall_s")

    def __init__(self, prompt: Sequence[int], max_new: int):
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.event = threading.Event()
        self.tokens: Optional[List[int]] = None
        self.error: Optional[str] = None
        self.truncated = False
        self.model_seq: Optional[int] = None
        self.t0 = time.perf_counter()
        self.ttft_s: Optional[float] = None
        #: the TTFT split (benchmarks/serving.py): time queued before the
        #: winning admission pass vs the prefill call wall (dispatch +
        #: first-token sync). ttft_s ~= queue_wait_s + prefill_wall_s.
        self.queue_wait_s: Optional[float] = None
        self.prefill_wall_s: Optional[float] = None


class _Slot:
    __slots__ = ("state", "req", "pos", "table", "gen", "gen_toks",
                 "stalled", "pending")

    def __init__(self):
        self.state = FREE
        self.req: Optional[DecodeRequest] = None
        self.pos = 0
        self.table: List[int] = []
        self.gen = 0
        #: generated tokens, in order. Plain mode: device refs — (array,
        #: idx) picks ``array[idx]``, idx None means a scalar array (values
        #: fetched only at retire/refill). Spec mode: plain host ints (the
        #: drafter needs host values every tick anyway).
        self.gen_toks: List[Any] = []
        self.stalled = False
        #: spec mode only: the pending token (sampled, K/V not yet
        #: written) as a host int — window position 0 of the next verify.
        self.pending: Optional[int] = None


class DecodeEngine:
    """Continuous batching over one model config. Weights come from a
    ``ModelRegistry`` (hot-swappable) or a statically installed params
    pytree (``install_params`` — each call counts as a swap, which is how
    the swap-mid-decode tests drive both policies without a CAS store)."""

    def __init__(self, cfg, registry=None, params=None, *,
                 slots: Optional[int] = None,
                 block_size: Optional[int] = None,
                 pool_blocks: Optional[int] = None,
                 max_blocks_per_slot: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 swap_policy: Optional[str] = None,
                 mesh=None, tp_axis: str = "tp",
                 spec_k: Optional[int] = None,
                 draft_fn: Optional[Callable[[Sequence[int], int],
                                             Sequence[int]]] = None):
        import jax
        from ..models import decode as MD
        from .server import pad_to_bucket

        self.cfg = cfg
        self.registry = registry
        if mesh is None:
            tp_knob = SC.decode_tp()
            if tp_knob > 1:
                from ..parallel.mesh import create_mesh
                mesh = create_mesh({tp_axis: tp_knob},
                                   devices=jax.devices()[:tp_knob])
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.tp = int(mesh.shape[tp_axis]) if mesh is not None else 1
        if mesh is not None:
            MD.validate_tp(cfg, self.tp)
        self._pad_to_bucket = pad_to_bucket
        self.n_slots = SC.decode_slots() if slots is None else int(slots)
        self.block_size = SC.decode_block_size() if block_size is None \
            else int(block_size)
        n_blocks = SC.decode_pool_blocks() if pool_blocks is None \
            else int(pool_blocks)
        self.max_blocks_per_slot = SC.decode_max_blocks_per_slot() \
            if max_blocks_per_slot is None else int(max_blocks_per_slot)
        self.prefill_buckets = tuple(sorted(
            int(b) for b in (prefill_buckets or SC.decode_prefill_buckets())))
        self.swap_policy = swap_policy or SC.decode_swap_policy()
        if self.swap_policy not in ("refill", "drain"):
            raise ValueError(f"swap policy {self.swap_policy!r}: use "
                             "'refill' or 'drain'")
        for b in self.prefill_buckets:
            if b % self.block_size:
                raise ValueError(f"prefill bucket {b} not a multiple of "
                                 f"block_size {self.block_size}")
        k = SC.decode_spec_k() if spec_k is None else int(spec_k)
        #: speculative window width; < 2 normalizes to 0 (off) — a K of 1
        #: would be the plain path with an extra host fetch for nothing.
        self.spec_k = k if k >= 2 else 0
        self._draft_fn = draft_fn
        #: host tokens emitted so far (both paths) — the spec bench's
        #: tokens/s numerator (token-slope over interleaved windows).
        self.tokens_emitted = 0
        self.max_context = self.max_blocks_per_slot * self.block_size
        if self.prefill_buckets[-1] > self.max_context:
            raise ValueError(
                f"largest prefill bucket {self.prefill_buckets[-1]} exceeds "
                f"per-slot context {self.max_context} "
                f"(max_blocks_per_slot * block_size)")

        self.allocator = BlockAllocator(n_blocks)
        self.slots = [_Slot() for _ in range(self.n_slots)]
        self._pending: "collections.deque[DecodeRequest]" = \
            collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._closing = False

        self._model_seq: Optional[int] = 0 if params is not None else None
        self._installed_seq = 0 if params is not None else None
        self._drain_target = None   # (params, seq) awaiting idle adoption

        #: trace-time side-effect counters — each increment runs ONCE per
        #: compile, so steady state pins ``decode`` exactly (the guardrail)
        self.compile_counts = {"decode": 0, "prefill": 0}
        if self.spec_k:
            # K = 0 never builds the verify program — the compile_counts
            # dict itself is the byte-identity witness (guardrail pins
            # exact dict equality at spec off).
            self.compile_counts["verify"] = 0
        if mesh is not None:
            _base_decode = MD.make_decode_step_tp(cfg, self.block_size,
                                                  mesh, tp_axis)
            _base_prefill = MD.make_prefill_tp(cfg, self.block_size,
                                               mesh, tp_axis)
            _base_verify = MD.make_verify_step_tp(
                cfg, self.block_size, mesh, tp_axis) if self.spec_k else None
        else:
            _base_decode = MD.make_decode_step(cfg, self.block_size)
            _base_prefill = MD.make_prefill(cfg, self.block_size)
            _base_verify = MD.make_verify_step(
                cfg, self.block_size) if self.spec_k else None

        def _decode_traced(p, kp, vp, toks, pos, tables, active):
            self.compile_counts["decode"] += 1
            return _base_decode(p, kp, vp, toks, pos, tables, active)

        def _prefill_traced(p, kp, vp, toks, block_ids):
            self.compile_counts["prefill"] += 1
            return _base_prefill(p, kp, vp, toks, block_ids)

        def _verify_traced(p, kp, vp, toks, pos, tables, active):
            self.compile_counts["verify"] += 1
            return _base_verify(p, kp, vp, toks, pos, tables, active)

        self._jnp = jax.numpy
        self._kp, self._vp = MD.init_kv_pools(cfg, n_blocks, self.block_size)
        self._dev_tokens = self._jnp.zeros((self.n_slots,), self._jnp.int32)
        if mesh is not None:
            # Pools live head-sharded on the mesh, with their row-major
            # layout PINNED at the jit boundary: entry layouts are chosen
            # by jit itself, and its heuristic can transpose whole pools
            # around the page gathers (the r4 DLRM trap).
            from jax.experimental.layout import Format, Layout
            from jax.sharding import NamedSharding, PartitionSpec as P
            # UNSPECIFIED = "let XLA choose" (None would replicate)
            from jax._src.sharding_impls import UNSPECIFIED as _u
            pool_nd = NamedSharding(mesh, MD.kv_pool_spec(tp_axis))
            pool_fmt = Format(Layout((0, 1, 2, 3, 4)), pool_nd)
            self._kp = jax.device_put(self._kp, pool_nd)
            self._vp = jax.device_put(self._vp, pool_nd)
            self._dev_tokens = jax.device_put(
                self._dev_tokens, NamedSharding(mesh, P()))
            self._decode = jax.jit(
                _decode_traced, donate_argnums=(1, 2),
                in_shardings=(_u, pool_fmt, pool_fmt, _u, _u, _u, _u),
                out_shardings=(_u, _u, pool_fmt, pool_fmt))
            self._prefill = jax.jit(
                _prefill_traced, donate_argnums=(1, 2),
                in_shardings=(_u, pool_fmt, pool_fmt, _u, _u),
                out_shardings=(_u, pool_fmt, pool_fmt))
            if self.spec_k:
                self._verify = jax.jit(
                    _verify_traced, donate_argnums=(1, 2),
                    in_shardings=(_u, pool_fmt, pool_fmt, _u, _u, _u, _u),
                    out_shardings=(_u, _u, pool_fmt, pool_fmt))
        else:
            self._decode = jax.jit(_decode_traced, donate_argnums=(1, 2))
            self._prefill = jax.jit(_prefill_traced, donate_argnums=(1, 2))
            if self.spec_k:
                self._verify = jax.jit(_verify_traced,
                                       donate_argnums=(1, 2))
        self._params = self._place_params(params)
        self._positions = np.zeros(self.n_slots, np.int32)
        self._tables = np.zeros((self.n_slots, self.max_blocks_per_slot),
                                np.int32)
        self._active = np.zeros(self.n_slots, bool)
        # Device mirrors of the block tables and the runnable mask: both
        # change only on admit/retire/extend/refill, not per tick, so the
        # step path skips two host->device uploads per tick (the upload
        # cost is pure overhead the verify window cannot amortize).
        self._tables_dev = None
        self._runnable_host: Optional[np.ndarray] = None
        self._runnable_dev = None

    # -- weights --------------------------------------------------------------

    def _place_params(self, params):
        """Mesh mode: land every leaf in its megatron sharding
        (``decode_param_specs``) — a no-op for leaves the registry's
        sharding-aware ``prepare_leaf`` already placed, so adoption never
        replicates-then-reshards. Single-device mode passes through."""
        if self.mesh is None or params is None:
            return params
        import jax
        from jax.sharding import NamedSharding
        from ..models import decode as MD
        specs = MD.decode_param_specs(self.cfg, params, self.tp_axis)
        return jax.tree.map(
            lambda leaf, s: jax.device_put(
                leaf, NamedSharding(self.mesh, s)), params, specs)

    def install_params(self, params) -> None:
        """Static-weights mode: (re)install a params pytree; each call
        after the first is observed as a hot-swap by the step loop."""
        with self._lock:
            self._installed = params
            self._installed_seq = (self._installed_seq or 0) + 1
        self._work.set()

    def _current(self):
        """(params, seq) from the registry, install_params, or the
        constructor params — one RCU read, no lock on the step path. A
        registry that has published nothing yet does not hide weights the
        engine was built with (``InferenceServer`` hands every engine its
        registry)."""
        cur = self.registry.current() if self.registry is not None else None
        if cur is not None:
            return cur.payload, cur.manifest_seq
        if getattr(self, "_installed", None) is not None:
            return self._installed, self._installed_seq
        return self._params, self._model_seq

    # -- submission -----------------------------------------------------------

    def submit(self, prompt: Sequence[int],
               max_new: Optional[int] = None) -> DecodeRequest:
        req = DecodeRequest(prompt, SC.decode_max_new() if max_new is None
                            else max_new)
        if not req.prompt or req.max_new < 1:
            req.error = "empty prompt or max_new < 1"
            req.event.set()
            return req
        # Spec mode reserves K-1 extra positions: the LAST verify window
        # may start at the final budgeted position and still index
        # pos..pos+K-1 into the block table — the window-fit rule that
        # keeps take_along_axis in bounds (models/decode.py verify).
        window_slack = self.spec_k - 1 if self.spec_k else 0
        if len(req.prompt) > self.prefill_buckets[-1] \
                or len(req.prompt) + req.max_new + window_slack \
                > self.max_context:
            req.error = (f"request needs {len(req.prompt)}+{req.max_new}"
                         + (f"+{window_slack} (speculative window)"
                            if window_slack else "")
                         + f" positions; max prompt bucket "
                         f"{self.prefill_buckets[-1]}, context "
                         f"{self.max_context}")
            req.event.set()
            return req
        bucket = self._pad_to_bucket(len(req.prompt), self.prefill_buckets)
        if bucket // self.block_size > self.allocator.n_blocks - 1:
            # Admission could never succeed even on an idle pool — fail
            # fast instead of queueing forever.
            req.error = (f"prompt bucket {bucket} needs "
                         f"{bucket // self.block_size} blocks; pool has "
                         f"{self.allocator.n_blocks - 1}")
            req.event.set()
            return req
        with self._lock:
            self._pending.append(req)
        _telemetry.set_gauge("hvd_serving_decode_queue_depth",
                             float(len(self._pending)))
        self._work.set()
        return req

    def has_work(self) -> bool:
        return bool(self._pending) or bool(self._active.any())

    @property
    def active_slots(self) -> int:
        return int(self._active.sum())

    # -- the step loop --------------------------------------------------------

    def _runnable(self) -> np.ndarray:
        return self._active & ~np.asarray(
            [s.stalled for s in self.slots])

    def _tables_device(self):
        if self._tables_dev is None:
            self._tables_dev = self._jnp.asarray(self._tables)
        return self._tables_dev

    def _runnable_device(self, runnable: np.ndarray):
        if self._runnable_host is None \
                or not np.array_equal(runnable, self._runnable_host):
            self._runnable_host = runnable.copy()
            self._runnable_dev = self._jnp.asarray(runnable)
        return self._runnable_dev

    def decode_once(self) -> bool:
        """One engine tick: observe swaps, admit, step every active slot.
        Returns True when a decode step ran."""
        self._observe_swap()
        self._admit_pending()
        if not self._active.any():
            return False
        self._extend_tables()
        runnable = self._runnable()
        if not runnable.any():
            # Every active slot is stalled on a block extension with the
            # free list empty: no step can run, so no retire can ever free
            # blocks — a permanent deadlock (and a leak) unless broken.
            # Retire the longest stalled sequence truncated; its blocks
            # unstall the rest.
            if self.allocator.free_blocks == 0:
                self._break_stall()
                self._extend_tables()
                runnable = self._runnable()
            if not runnable.any():
                return False
        if self.spec_k:
            return self._spec_step(runnable)
        jnp = self._jnp
        runnable_dev = self._runnable_device(runnable)
        logits, nt, self._kp, self._vp = self._decode(
            self._params, self._kp, self._vp, self._dev_tokens,
            jnp.asarray(self._positions), self._tables_device(),
            runnable_dev)
        del logits  # sampling is on-device (greedy argmax in the program)
        # Masked slots (inactive OR stalled) must keep their pending token:
        # a stalled slot's nt row came from an un-extended table (its K/V
        # landed in the null block), and consuming it on unstall would
        # silently fork the stream from greedy.
        self._dev_tokens = jnp.where(runnable_dev, nt, self._dev_tokens)
        stepped = 0
        for i, slot in enumerate(self.slots):
            if not runnable[i]:
                continue
            slot.gen_toks.append((nt, i))
            slot.gen += 1
            slot.pos += 1
            self._positions[i] = slot.pos
            stepped += 1
            if slot.gen >= slot.req.max_new:
                self._retire(i)
        self.tokens_emitted += stepped
        _telemetry.inc("hvd_serving_decode_tokens_total", float(stepped))
        _telemetry.set_gauge("hvd_serving_decode_active_slots",
                             float(self.active_slots))
        _telemetry.set_gauge("hvd_serving_decode_free_blocks",
                             float(self.allocator.free_blocks))
        return True

    # -- speculative tick -----------------------------------------------------

    def _draft(self, slot: _Slot, n: int) -> List[int]:
        """``n`` candidate tokens for ``slot`` from the injected
        ``draft_fn`` (bench's adversarial arm) or the built-in n-gram
        lookup. Host-only by contract (``lint-host-draft-loop``)."""
        ctx = slot.req.prompt + slot.gen_toks
        if self._draft_fn is not None:
            cand = [int(t) for t in self._draft_fn(ctx, n)]
            if len(cand) < n:
                pad = cand[-1] if cand else (int(ctx[-1]) if ctx else 0)
                cand += [pad] * (n - len(cand))
            return cand[:n]
        return _ngram_draft(ctx, n)

    def _spec_step(self, runnable: np.ndarray) -> bool:
        """One speculative tick over the runnable slots: draft on host,
        verify all K window positions in ONE program call, accept the
        longest matching prefix, rewind positions to the accepted length.

        Window row i = ``[pending, d_1 .. d_{K-1}]`` at positions
        ``pos .. pos+K-1``; the program's ``g[i, j]`` is the greedy token
        after consuming window token j, so ``g[i, 0]`` is always the TRUE
        next token and draft ``d_j`` is accepted iff ``d_j == g[i, j-1]``
        with every earlier draft accepted. Emitting ``g[i, :n_acc+1]`` is
        therefore bit-identical to running the plain decode loop
        ``n_acc+1`` times — lossless by construction. The single
        ``np.asarray`` below is the one host fetch speculation inherently
        needs (drafting consumes host tokens); it replaces the plain
        path's zero-fetch feedback but the verify call amortizes the
        weight read over every accepted token.
        """
        jnp = self._jnp
        K = self.spec_k
        vmax = int(self.cfg.vocab_size) - 1
        toks = np.zeros((self.n_slots, K), np.int32)
        for i, slot in enumerate(self.slots):
            if not runnable[i]:
                continue
            toks[i, 0] = slot.pending
            # Clamp drafts into vocab: an out-of-range id from an injected
            # drafter would hit jnp.take's fill mode → NaN embedding → NaN
            # K/V rows that poison even MASKED attention (0 · NaN = NaN).
            # Acceptance below compares the clamped value actually
            # verified, so clamping stays lossless.
            toks[i, 1:] = np.clip(self._draft(slot, K - 1), 0, vmax)
        if self.mesh is None:
            # One batched transfer for the two per-tick host arrays: the
            # spec tick syncs on its host fetch every tick (acceptance
            # needs g), so upload latency is serial — measured ~55us/tick
            # cheaper batched than two jnp.asarray calls.
            import jax
            toks_dev, pos_dev = jax.device_put((toks, self._positions))
        else:
            toks_dev = jnp.asarray(toks)
            pos_dev = jnp.asarray(self._positions)
        logits, g, self._kp, self._vp = self._verify(
            self._params, self._kp, self._vp, toks_dev, pos_dev,
            self._tables_device(), self._runnable_device(runnable))
        del logits              # greedy argmax is in the program
        g_h = np.asarray(g)     # the one [S, K] host fetch per tick
        # Longest-matching-prefix lengths for ALL slots at once: draft
        # d_{j+1} is accepted iff it equals g[:, j] with every earlier
        # draft accepted — a leading-True run length per row.
        n_accs = np.cumprod(toks[:, 1:] == g_h[:, :-1], axis=1).sum(axis=1)
        stepped = 0
        hits = 0
        n_run = 0
        for i, slot in enumerate(self.slots):
            if not runnable[i]:
                continue
            n_run += 1
            n_acc = int(n_accs[i])
            # g[i, :n_acc] re-derives the accepted drafts; position
            # n_acc is the first novel token. Budget can cap the emit
            # below the accepted length (the slot retires regardless).
            n_emit = min(n_acc + 1, slot.req.max_new - slot.gen)
            new = [int(t) for t in g_h[i, :n_emit]]
            slot.gen_toks.extend(new)
            slot.pending = new[-1]
            slot.gen += n_emit
            # The REWIND: positions advance by the accepted length only;
            # every window row past it holds stale K/V the next verify
            # (starting at the new pos) overwrites before any causal
            # mask can admit it (tests/test_spec_decode.py).
            slot.pos += n_emit
            self._positions[i] = slot.pos
            stepped += n_emit
            hits += n_acc
            _telemetry.observe("hvd_serving_spec_accept_len", float(n_acc))
            if slot.gen >= slot.req.max_new:
                self._retire(i)
        self.tokens_emitted += stepped
        _telemetry.inc("hvd_serving_decode_tokens_total", float(stepped))
        _telemetry.inc("hvd_serving_spec_draft_hits_total", float(hits))
        _telemetry.inc("hvd_serving_spec_draft_tokens_total",
                       float(n_run * (K - 1)))
        _telemetry.set_gauge("hvd_serving_decode_active_slots",
                             float(self.active_slots))
        _telemetry.set_gauge("hvd_serving_decode_free_blocks",
                             float(self.allocator.free_blocks))
        return True

    # -- admission / retirement ----------------------------------------------

    def _admit_pending(self) -> None:
        if self._drain_target is not None:
            return                      # draining: no admissions
        while self._pending:
            idx = next((i for i, s in enumerate(self.slots)
                        if s.state == FREE), None)
            if idx is None:
                return
            params, seq = self._current()
            if params is None:
                return                  # nothing published yet
            if seq != self._model_seq:
                # A swap landed between this tick's _observe_swap and
                # admission. Adopting here would put live slots' OLD-weights
                # KV pages under NEW weights with no refill/drain — defer
                # to the next tick so _observe_swap applies the policy.
                return
            with self._lock:
                if not self._pending:
                    return
                req = self._pending.popleft()
            bucket = self._pad_to_bucket(len(req.prompt),
                                         self.prefill_buckets)
            blocks = self.allocator.alloc_many(bucket // self.block_size)
            if blocks is None:
                with self._lock:
                    self._pending.appendleft(req)
                _telemetry.inc("hvd_serving_decode_admit_stalls_total")
                return                  # pool exhausted: retry next tick
            # TTFT split: everything before this instant is queue wait
            # (batching, slot/pool contention, deferred swaps); everything
            # after is the prefill wall (dispatch + first-token sync).
            t_adm = time.perf_counter()
            req.queue_wait_s = t_adm - req.t0
            ft = self._run_prefill(req.prompt, blocks, bucket)
            slot = self.slots[idx]
            slot.state = ACTIVE
            slot.req = req
            slot.pos = len(req.prompt)
            slot.table = blocks
            slot.gen = 1
            slot.stalled = False
            self._positions[idx] = slot.pos
            self._tables[idx] = 0
            self._tables[idx, :len(blocks)] = blocks
            self._tables_dev = None
            self._active[idx] = True
            self._dev_tokens = self._dev_tokens.at[idx].set(ft)
            # TTFT is honest: the first token is materialized before the
            # request is declared admitted (prefill is the one place the
            # engine may sync — never the decode loop)
            ft.block_until_ready()
            req.ttft_s = time.perf_counter() - req.t0
            req.prefill_wall_s = time.perf_counter() - t_adm
            if self.spec_k:
                # Spec mode keeps HOST tokens: the prefill token is both
                # the first emitted token and the pending window head.
                tok0 = int(ft)
                slot.gen_toks = [tok0]
                slot.pending = tok0
            else:
                slot.gen_toks = [(ft, None)]
            _telemetry.inc("hvd_serving_decode_admitted_total")
            _telemetry.observe("hvd_serving_decode_ttft_seconds", req.ttft_s)
            if slot.gen >= req.max_new:
                self._retire(idx)

    def _run_prefill(self, prompt: Sequence[int], blocks: Sequence[int],
                     bucket: int):
        """Prefill ``prompt`` into ``blocks``; returns the first generated
        token as a DEVICE scalar."""
        jnp = self._jnp
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        logits, self._kp, self._vp = self._prefill(
            self._params, self._kp, self._vp, jnp.asarray(padded),
            jnp.asarray(np.asarray(blocks, np.int32)))
        return jnp.argmax(logits[0, len(prompt) - 1]).astype(jnp.int32)

    def _extend_tables(self) -> None:
        """Grow any slot whose next WRITE WINDOW crosses into unallocated
        blocks — one position per tick plain, ``spec_k`` positions under
        speculation (the whole verify window must be backed before the
        call: every window row is scattered, accepted or not); a slot
        that cannot get every block it needs STALLS (masked out) until a
        retire frees capacity — never a recompile, never an OOM. Partial
        extensions keep their blocks (they stay in the table for the
        retry). If EVERY active slot stalls with the free list empty no
        retire could ever happen; ``decode_once`` breaks that deadlock
        via ``_break_stall``."""
        window = self.spec_k if self.spec_k else 1
        for i, slot in enumerate(self.slots):
            if slot.state != ACTIVE:
                continue
            need = (slot.pos + window - 1) // self.block_size
            while need >= len(slot.table):
                b = self.allocator.alloc()
                if b is None:
                    break
                slot.table.append(b)
                self._tables[i, len(slot.table) - 1] = b
                self._tables_dev = None
            if need < len(slot.table):
                slot.stalled = False
            elif not slot.stalled:
                slot.stalled = True
                _telemetry.inc("hvd_serving_decode_block_stalls_total")

    def _break_stall(self) -> None:
        """All active slots stalled with zero free blocks: retire the
        longest sequence truncated (it has the most tokens to deliver and
        frees the most blocks) so the remaining slots can extend."""
        idx = max((i for i, s in enumerate(self.slots) if s.state == ACTIVE),
                  key=lambda i: self.slots[i].pos, default=None)
        if idx is None:
            return
        get_logger().warning(
            "decode pool deadlocked (all %d active slots stalled, 0 free "
            "blocks): retiring slot %d truncated at pos %d",
            self.active_slots, idx, self.slots[idx].pos)
        _telemetry.inc("hvd_serving_decode_stall_breaks_total")
        self._retire(idx, truncated=True)

    def _slot_token_values(self, slot: _Slot) -> List[int]:
        """Fetch the slot's generated tokens (host sync — retire/refill
        paths only, never the decode loop). Spec-mode entries are already
        host ints; plain-mode entries are device refs."""
        if not slot.gen_toks:
            return []
        if isinstance(slot.gen_toks[0], int):
            return list(slot.gen_toks)
        vals = np.asarray(self._jnp.stack(
            [a if i is None else a[i] for a, i in slot.gen_toks]))
        return [int(v) for v in vals]

    def _retire(self, idx: int, truncated: bool = False) -> None:
        slot = self.slots[idx]
        req = slot.req
        req.tokens = req.prompt + self._slot_token_values(slot)
        req.truncated = truncated
        req.model_seq = self._model_seq
        req.event.set()
        self.allocator.free(slot.table)
        slot.state = FREE
        slot.req = None
        slot.table = []
        slot.gen_toks = []
        slot.stalled = False
        slot.pending = None
        slot.pos = 0
        slot.gen = 0
        self._active[idx] = False
        self._positions[idx] = 0
        self._tables[idx] = 0
        self._tables_dev = None
        _telemetry.inc("hvd_serving_decode_retired_total")
        if self._drain_target is not None and not self._active.any():
            tgt_params, tgt_seq = self._drain_target
            self._params, self._model_seq = \
                self._place_params(tgt_params), tgt_seq
            self._drain_target = None
            _telemetry.inc("hvd_serving_decode_drain_adoptions_total")

    # -- hot-swap -------------------------------------------------------------

    def _observe_swap(self) -> None:
        params, seq = self._current()
        if params is None or seq == self._model_seq:
            return
        if self._model_seq is None or not self._active.any():
            # trivial adoption
            self._params, self._model_seq = self._place_params(params), seq
            self._drain_target = None
            return
        if self.swap_policy == "drain":
            self._drain_target = (params, seq)
            return
        # refill: adopt now, remap every live slot's blocks under the new
        # weights (the p99-latency-under-swap cost the bench rails)
        self._params, self._model_seq = self._place_params(params), seq
        self._drain_target = None
        t0 = time.perf_counter()
        n = self._refill_live_slots()
        _telemetry.inc("hvd_serving_decode_refills_total", float(n))
        _telemetry.observe("hvd_serving_decode_refill_seconds",
                           time.perf_counter() - t0)

    def _refill_live_slots(self) -> int:
        refilled = 0
        for i, slot in enumerate(self.slots):
            if slot.state != ACTIVE:
                continue
            seq_toks = slot.req.prompt + self._slot_token_values(slot)
            if len(seq_toks) > self.prefill_buckets[-1]:
                # sequence has outgrown the prefill program set: finish it
                # with what it has rather than serve mixed-generation KV
                self._retire(i, truncated=True)
                continue
            bucket = self._pad_to_bucket(len(seq_toks),
                                         self.prefill_buckets)
            self.allocator.free(slot.table)
            slot.table = []
            blocks = self.allocator.alloc_many(bucket // self.block_size)
            if blocks is None:          # cannot re-place: finish early
                self._retire(i, truncated=True)
                continue
            ft = self._run_prefill(seq_toks, blocks, bucket)
            slot.table = blocks
            slot.pos = len(seq_toks)
            slot.gen += 1
            if self.spec_k:
                tok0 = int(ft)
                slot.gen_toks.append(tok0)
                slot.pending = tok0
            else:
                slot.gen_toks.append((ft, None))
            self._positions[i] = slot.pos
            self._tables[i] = 0
            self._tables[i, :len(blocks)] = blocks
            self._tables_dev = None
            self._dev_tokens = self._dev_tokens.at[i].set(ft)
            refilled += 1
            if slot.gen >= slot.req.max_new:
                self._retire(i)
        return refilled

    # -- background serving --------------------------------------------------

    def start(self) -> None:
        """Run the step loop on a daemon thread (server integration)."""
        if self._thread is not None:
            return

        def _loop():
            while not self._closing:
                try:
                    # Wait whenever no step ran — even with work pending
                    # (e.g. admission blocked on the pool or on a swap):
                    # nothing changes until a tick or an external event
                    # sets _work, so spinning would just burn the core.
                    if not self.decode_once():
                        self._work.wait(timeout=0.05)
                        self._work.clear()
                except Exception as err:  # noqa: BLE001 — containment
                    get_logger().error("decode engine tick failed: %s", err)
                    self._fail_all(str(err))

        self._thread = threading.Thread(target=_loop, name="hvd-decode",
                                        daemon=True)
        self._thread.start()

    def _fail_all(self, msg: str) -> None:
        with self._lock:
            pending = list(self._pending)
            self._pending.clear()
        for i, slot in enumerate(self.slots):
            if slot.state == ACTIVE:
                slot.req.error = msg
                slot.req.event.set()
                self.allocator.free(slot.table)
                slot.state = FREE
                slot.req = None
                slot.table = []
                slot.gen_toks = []
                self._active[i] = False
        for req in pending:
            req.error = msg
            req.event.set()

    def close(self) -> None:
        self._closing = True
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def run_until_idle(self, max_steps: int = 10_000) -> None:
        """Drive the loop inline until every request completes (tests)."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.decode_once()
        raise RuntimeError(f"engine still busy after {max_steps} steps")


# -- per-shard CAS glue (docs/checkpointing.md "Per-shard blobs") -------------
#
# Three small factories tie the decode plane's megatron plan
# (``models/decode.py::decode_leaf_shard_axis`` — the single source of
# truth for which array axis a leaf splits on) to the CAS seams:
# ``tp_shard_plan`` feeds a Publisher's shard writer, ``tp_shard_selector``
# a replica host's delta-fetching registry, ``tp_prepare_leaf`` the
# sharding-aware leaf placement for a mesh-mode engine's registry.

def tp_shard_plan(tp: int):
    """``shard_plan`` for :class:`serving.publisher.Publisher`: split
    every tp-sharded decode leaf into ``tp`` parts along its plan axis;
    replicated (or indivisible) leaves keep whole-leaf blobs only."""
    from ..models import decode as MD

    def plan(path_names, shape):
        ax = MD.decode_leaf_shard_axis(path_names, shape, tp)
        return None if ax is None else (ax, tp)

    return plan


def tp_shard_selector(tp: int, shard_index: int):
    """``shard_selector`` for :class:`serving.registry.ModelRegistry` on
    the replica host holding shard ``shard_index`` of a ``tp``-wide
    decode mesh: fetch exactly its part of each sharded leaf. A manifest
    sharded for a DIFFERENT topology (``n != tp``) falls back to the
    whole-leaf blob — read-compatibility under topology changes."""
    if not 0 <= shard_index < tp:
        raise ValueError(f"shard_index {shard_index} outside tp={tp}")

    def selector(path_names, shard_meta):
        if int(shard_meta.get("n", 0)) != tp:
            return None
        return [shard_index]

    return selector


def tp_prepare_leaf(cfg, mesh, tp_axis: str = "tp"):
    """Sharding-aware ``prepare_leaf`` for a registry feeding a mesh-mode
    engine: each newly fetched leaf lands in its megatron sharding in ONE
    ``device_put`` — never replicated first and resharded by the engine
    (the adopt-path placement bugfix). Cache hits keep their placed
    object across swaps, so unchanged leaves stay zero-copy."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..models import decode as MD

    tp = int(mesh.shape[tp_axis])
    MD.validate_tp(cfg, tp)

    def prepare(leaf, path_names):
        ax = MD.decode_leaf_shard_axis(path_names, np.shape(leaf), tp)
        spec = P() if ax is None else P(*([None] * ax + [tp_axis]))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return prepare
