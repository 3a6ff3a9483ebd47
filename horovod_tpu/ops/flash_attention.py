"""Pallas TPU flash attention with ring-mergeable softmax residuals.

Capability-NEW vs the reference (SURVEY.md §5.7): the reference never touches
activations, so it has no attention kernel at all. This is the hot-op half of
the framework's long-context story (parallel/ring.py is the collective half):
a blockwise-softmax attention kernel that keeps the [Tq, Tk] score matrix out
of HBM entirely — each (q-block, k-block) tile is produced in VMEM, folded
into running (max, denominator, accumulator) state, and discarded. Scores hit
the MXU as [block_q, D] x [D, block_k] matmuls in fp32.

``v`` may have a depth of its own (``Dv``; latent attention: 192-deep
queries and keys, 128-deep values): o, dO and dV then have ``v``'s depth and
dQ, dK the query's, in every kernel below.

Two properties matter for the distributed design:

- **Residuals** (``return_residuals=True``): the kernel can return the
  running max ``m`` and denominator ``l`` alongside the normalised output, so
  two partial attentions over disjoint key sets can be combined *exactly*
  with :func:`merge_partials`. That is precisely what ring attention needs —
  each ppermute step computes a partial against the resident K/V shard and
  merges it into the carry, so the kernel composes with the ICI ring without
  any cross-step state inside the kernel.
- **Causal block skipping**: with ``causal=True`` tiles strictly above the
  diagonal are predicated off with ``pl.when``, saving ~half the MXU work.

The forward (``hvd_flash_fwd``, grid ``(batch*heads, nq, nk)``) keeps its
running statistics WHOLE across the 128 lanes: ``m`` and the correction
``exp(m_prev - m_new)`` as ``[rows, 128]`` arrays with every lane of a row
alike, read, updated and stored without a slice or a broadcast, and the
denominator, where a key tile is whole 128-lane tiles, as a row's 128
per-lane partial sums (lane j gathers the probabilities of columns j,
j + 128, ... by elementwise adds), added up across lanes once a query tile
in the last visit. As ``(rows, 1)`` columns each use of a statistic went
through the cross-lane unit, and on the v5e that unit, not the array, set a
visit's time: at 4,096 causal tokens the call fell from 7.12 to 4.21 ms at
192/128 depth and from 1.385 to 0.635 at 128, to within 3% of its two dots
alone (PERF.md section 6, PR 34). ``m`` and every ``o`` of a one-tile
sequence keep their bits; over several key tiles a row's denominator is
added in another order (5 float32 places at most measured, one bfloat16
place of ``o`` in a hundred thousandth of the elements), and ``l`` as
returned is still the whole row's sum, which :func:`merge_partials` needs.
A visit is computed in row groups (``_fwd_groups``, from the tile's static
shape): two halves where a 512-row tile meets the sequence's one key tile,
one group otherwise; rows never mix, so the groups change no number. The
trace-time counter
``hvd_flash_fwd_kernels_total{groups="1"|"2", denominator="lanes"|"rows"}``
says which a program got.

The backward pass recomputes probability tiles from the saved
(q, k, v, o, m, l) — the standard flash trade of FLOPs for HBM (SURVEY.md §7
lists remat as the stock TPU memory lever). Two implementations exist:
dedicated Pallas kernels on the common ``return_residuals=False`` model
path, and a materialised-softmax jnp recompute vjp kept for
``return_residuals=True``, where the (m, l) outputs carry real cotangents
from ring-attention partial merging that the kernels do not model.

The kernel backward adapts to the shape it is traced with
(``_fa_bwd_pallas``, no option selects it) and has three forms. Each
recomputes the probability tile ``p`` and the score cotangent ``ds`` from
the saved statistics; they differ in how often.

- ONE tile in both directions (``Tq <= block_q`` and ``Tk <= block_k``: up
  to 512 tokens at the default blocks): nothing accumulates across tiles,
  so one fused kernel (``hvd_flash_bwd``) on grid ``(batch*heads,)``
  recomputes the tile once and writes dQ, dK and dV from it, with no
  scratch and no ``pl.when``.
- Several tiles, and one (batch*head)'s dQ row fits VMEM beside the tiles
  (``_tiled_bwd_vmem_bytes <= _VMEM_BYTES``: 16.5 MiB of 64 at 4,096 tokens
  and 192/128 depth, up to 57,344 tokens at 128-deep heads): one fused
  tiled kernel (``hvd_flash_bwd_tiled``) on grid ``(batch*heads, nk, nq)``,
  query tiles innermost. A visible tile computes ``p`` and ``ds`` ONCE and
  issues three dots from them: dV and dK accumulate over the inner axis,
  dQ over the outer one into a float32 scratch that holds the whole
  ``[Tq, D]`` row, so every sum keeps the order the two passes give it.
- Rows too long for that: the FlashAttention-2 split, a dQ pass
  (``hvd_flash_bwd_dq``) that accumulates over key tiles and a dK/dV pass
  (``hvd_flash_bwd_dkv``) that accumulates over query tiles, each
  recomputing the tile: 7 matmuls and two rounds of the T² elementwise
  work where the fused forms spend 5 and one.

The trace-time counter
``hvd_flash_bwd_kernels_total{kind="fused"|"fused_tiled"|"two_pass"}`` says
which a program got. The backward kernels read the saved ``m`` and ``l``
as ``(rows, 1)`` blocks and hide their softmax arithmetic under five dots.

**A window** (``flash_attention(..., window=W)``, sliding-window
attention): with ``causal`` and queries and keys of one sequence, a static
count of visible keys a query: key ``j`` is visible to query ``i`` iff
``i - W < j <= i``. It is a static fact of the call like ``causal``, with no
option beside it: the same kernel bodies take the mask (``_seen``) in the
forward, in every form of the backward, in ``_recompute_p_ds`` and in the
``jnp`` recompute path (``_reference_partial``), under the
``custom_partitioning`` wrappers too. What a window changes is the GRID,
which covers the band alone: a tile pair is visited iff it holds a visible
pair (``band_tiles``). The forward's and the dQ pass's inner axis is the
key tiles of a query tile's band (``_key_band``), the tiled backward's and
the dK/dV pass's the query tiles of a key tile's band (``_query_band``),
as long as the longest band (``_band_steps``: 2 steps at a window of one
block, where the unwindowed grid has ``nk``); the block specs' index maps
add the band's first tile to the step (``_band_step``), and a step past a
shorter band's end (the sequence's first tiles) holds the last tile's
block, fetches nothing and computes nothing. So a windowed call pays no
grid step for the tiles outside the band: at 8,192 tokens and a window of
512 in tiles of 512, 31 of a (batch·head)'s 256 tile pairs, and a call's
time is linear in the sequence's length (0.89 / 1.78 / 3.60 ms forward at
4,096 / 8,192 / 16,384 tokens and 36 heads against 7.10 unwindowed at
8,192: PERF.md section 6, PR 35). In the tiled backward a query tile's
rows of the float32 dQ scratch are zeroed in the first key tile of ITS band
and written out in the last; the key tiles come in ascending order as
before, so every sum keeps its order and the tiled kernel equals the two
passes bit for bit. Blocks stay 512 x 512 under a window (narrower key or
query blocks measured slower at 8,192 tokens: 256 x 256 2.94 / 4.09 ms
forward / backward against 1.78 / 3.19), so half of each band tile is
masked: ``flash_window_roofline`` counts that as time and not as work.
Windowed calls carry names of their own, ``KERNEL_FWD_WINDOW`` and
``KERNEL_BWD_WINDOW`` (the unwindowed names with ``_window`` appended), and
count themselves in ``hvd_flash_window_kernels_total{window=, band_tiles=,
kind="fwd"|"bwd_fused"|"bwd_tiled"|"bwd_two_pass"}``: one a windowed call of
the traced program, forward or backward (three sliding layers: three and
three), where the two counters above fire once a shape's cached trace. ``window=None``, or a window no
shorter than the sequence, is the unwindowed call: every kernel body, grid,
block spec and name above as it was.

On non-TPU backends the kernel runs in Pallas interpreter mode, which is how
the CPU test mesh exercises it (the reference's CPU+Gloo fake-backend trick,
SURVEY.md §4).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import telemetry as _telemetry

NEG_INF = -1e30  # finite mask value: exp() underflows cleanly, no NaN algebra

_LANE = 128

# What a compiled program and a profile's ``XLA Ops`` events call the kernels
# (XLA names the custom call after ``pallas_call``'s ``name``): per-kernel
# readers find them by these, whatever Python function encloses the call.
KERNEL_FWD = "hvd_flash_fwd"
KERNEL_BWD = ("hvd_flash_bwd_dq", "hvd_flash_bwd_dkv", "hvd_flash_bwd",
              "hvd_flash_bwd_tiled")
_BWD_DQ, _BWD_DKV, _BWD_FUSED, _BWD_TILED = KERNEL_BWD
# A call with a window carries names of its own, form by form in the order
# above, so that a reader of the unwindowed kernels' time sees none of it.
KERNEL_FWD_WINDOW = "hvd_flash_fwd_window"
KERNEL_BWD_WINDOW = tuple(name + "_window" for name in KERNEL_BWD)

# VMEM one kernel may hold: half of a TPU core's 128 MiB (v5e, v6e), the
# other half left to what XLA keeps in flight round the call. The several-tile
# fused backward runs while ``_tiled_bwd_vmem_bytes`` stays within it. A call
# that needs more than Mosaic's default scoped limit asks for its sum.
_VMEM_BYTES = 64 * 1024 * 1024
_VMEM_SCOPED_DEFAULT = 16 * 1024 * 1024

# Rows of a group where the forward splits a tile (``_fwd_groups``).
_GROUP_ROWS = 256


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _lanes(x, n):
    """``x`` [rows, 128] with every lane of a row alike, as [rows, n]: a lane
    slice, or whole 128-lane copies side by side. Neither crosses lanes;
    another width broadcasts lane 0."""
    if n <= _LANE:
        return x[:, :n]
    if n % _LANE == 0:
        return pltpu.repeat(x, n // _LANE, axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _band(i, size, other, n_other, back, ahead):
    """``(first, last)`` tile of the other axis that holds a position
    visible from tile ``i``: tile ``i`` spans positions ``[i * size,
    (i + 1) * size)``, and a position sees ``back`` positions behind it and
    ``ahead`` in front on the other axis, whose ``n_other`` tiles are
    ``other`` long. For a Python int as for a traced index."""
    big, small = (max, min) if isinstance(i, int) \
        else (jnp.maximum, jnp.minimum)
    lo = big(i * size - back, 0) // other
    hi = small(((i + 1) * size - 1 + ahead) // other, n_other - 1)
    return lo, hi


def _key_band(iq, bq, bk, nk, window):
    """Key tiles of a query tile's band: keys ``i - window < j <= i``."""
    return _band(iq, bq, bk, nk, window - 1, 0)


def _query_band(ik, bq, bk, nq, window):
    """Query tiles of a key tile's band: queries ``j <= i < j + window``."""
    return _band(ik, bk, bq, nq, 0, window - 1)


def _band_steps(n, band):
    """The inner grid axis of a windowed call: the most tiles any of the
    ``n`` outer tiles' bands holds."""
    return max(hi - lo + 1 for lo, hi in map(band, range(n)))


def band_tiles(nq, nk, bq, bk, window):
    """``(query tile, key tile)`` pairs a windowed call's grid computes:
    those that hold a visible pair, query-major."""
    return [(iq, ik) for iq in range(nq)
            for lo, hi in [_key_band(iq, bq, bk, nk, window)]
            for ik in range(lo, hi + 1)]


def _band_step(i, j, band):
    """Inner step ``j`` of outer tile ``i``: ``(the band's tile, whether the
    step is inside the band)``. A step past the band's end stays on the
    band's last tile, so its block is the one already held and nothing is
    fetched for it."""
    lo, hi = band(i)
    return jnp.minimum(lo + j, hi), lo + j <= hi


def _key_steps(nq, nk, block_q, block_k, window):
    """Inner axis of a grid whose key tiles are innermost (the forward's
    and the dQ pass's): ``(steps, key tile of inner step j of query tile
    i)``; every key tile, or with a ``window`` those of the query tile's
    band."""
    if window is None:
        return nk, lambda i, j: j
    band = lambda i: _key_band(i, block_q, block_k, nk, window)
    return _band_steps(nq, band), lambda i, j: _band_step(i, j, band)[0]


def _query_steps(nq, nk, block_q, block_k, window):
    """The same for a grid whose query tiles are innermost (the tiled
    backward's and the dK/dV pass's; ``q_of`` of ``_kv_major_specs``)."""
    if window is None:
        return nq, lambda i, j: j
    band = lambda i: _query_band(i, block_q, block_k, nq, window)
    return _band_steps(nk, band), lambda i, j: _band_step(i, j, band)[0]


def _seen(q_pos, k_pos, window):
    """The causal mask, with a window ``i - window < j <= i``."""
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _fa_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, bq, bk, nk,
               has_bias, groups, lane_sums, window=None, steps=None):
    """One (batch*head, q-block, k-block) grid step.

    Scratch (persists across the innermost k-block grid dim):
      acc [bq, Dv] f32 — unnormalised output accumulator
      m_s [bq, 128] f32 — running row max, every lane of a row alike
      l_s [bq, 128] f32 — running denominator: with ``lane_sums`` (``bk``
        is whole 128-lane tiles) a row's per-lane partial sums, which
        ``_emit`` adds up; every lane alike otherwise

    The statistics are read, updated and written whole, [rows, 128], so
    that no use of one goes through the cross-lane unit (module docstring):
    what crosses lanes is the row max, once a visit, and the denominator,
    once a query tile. The visit runs in ``groups`` row groups.
    """
    if has_bias:
        bias_ref, o_ref, m_ref, l_ref, acc, m_s, l_s = refs
    else:
        o_ref, m_ref, l_ref, acc, m_s, l_s = refs
    iq = pl.program_id(1)
    step = pl.program_id(2)
    g = bq // groups

    @pl.when(step == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    if window is None:
        ik, last = step, nk - 1
        # Causal: skip tiles strictly above the diagonal (no q position in
        # this block can see any k position in that block).
        visible = ((iq + 1) * bq - 1 >= ik * bk) if causal else (ik >= 0)
    else:
        # The inner axis walks the query tile's band and no other tile.
        ik, visible = _band_step(
            iq, step, lambda i: _key_band(i, bq, bk, nk, window))
        last = steps - 1

    def _visit(r0):
        """Rows ``r0 : r0 + g`` of the tile against the whole key block.
        Rows never mix, so the groups change no number."""
        rows = pl.ds(r0, g)
        # Keep the input dtype (bf16 on TPU) for both MXU dots and accumulate
        # in f32 via preferred_element_type — casting up first would force
        # fp32 MXU passes, ~4x the matmul cost for no accuracy the f32
        # accumulation doesn't already give.
        q = q_ref[0, rows, :]
        k = k_ref[0]
        v = v_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            q_pos = iq * bq + r0 + lax.broadcasted_iota(jnp.int32, (g, bk), 0)
            k_pos = ik * bk + lax.broadcasted_iota(jnp.int32, (g, bk), 1)
            s = jnp.where(_seen(q_pos, k_pos, window), s, NEG_INF)
        m_prev = m_s[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Rows still fully masked have m_new == NEG_INF; exp(s - m_new) would
        # be exp(0) = 1 there, so zero those probabilities explicitly.
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - _lanes(m_new, bk)))
        corr = jnp.exp(m_prev - m_new)
        if lane_sums:
            # lane j gathers columns j, j + 128, ...: elementwise adds
            p_sum = p[:, :_LANE]
            for c in range(_LANE, bk, _LANE):
                p_sum = p_sum + p[:, c:c + _LANE]
        else:
            p_sum = jnp.sum(p, axis=1, keepdims=True)
        l_s[rows, :] = l_s[rows, :] * corr + p_sum
        acc[rows, :] = acc[rows, :] * _lanes(corr, acc.shape[1]) \
            + lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        m_s[rows, :] = m_new

    @pl.when(visible)
    def _compute():
        for r0 in range(0, bq, g):
            _visit(r0)

    @pl.when(step == last)
    def _emit():
        if lane_sums:
            l = jnp.sum(l_s[:], axis=1, keepdims=True)
        else:
            l = l_s[:, :1]
        o_ref[0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        # Residuals are [BH, Tq, 1] so the block's trailing dims (bq, 1)
        # satisfy the TPU tiling rule (sublane divisible by 8, lane equal to
        # the array dim).
        m_ref[0] = m_s[:, :1]
        l_ref[0] = l


def _fwd_groups(block_q, nk):
    """Row groups the forward computes a visit in, from the tile's static
    shape alone: two of ``_GROUP_ROWS`` where a 512-row tile meets the
    sequence's ONE key tile, else one. Measured on the v5e (PERF.md section
    6, PR 34): halves are 4-9% faster than the whole tile when init, visit
    and emit share every grid step, and 3-8% slower across several key
    tiles, where each group pushes the key block into the array again;
    quarters lose everywhere."""
    if nk == 1 and block_q > _GROUP_ROWS and block_q % _GROUP_ROWS == 0:
        return block_q // _GROUP_ROWS
    return 1


def _pad_axis(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def _count_window_call(kind, window, Tq, Tk, block_q, block_k):
    """Trace-time: one a windowed call of the traced program, forward
    (``kind`` "fwd") or backward (by the form ``_bwd_kind`` picks). Counted
    at the call, outside ``_fa_call`` and ``_fa_bwd_pallas``, whose jit
    caches one trace for every call of a shape."""
    nq, nk = -(-Tq // block_q), -(-Tk // block_k)
    _telemetry.inc("hvd_flash_window_kernels_total", window=window,
                   band_tiles=len(band_tiles(nq, nk, block_q, block_k,
                                             window)), kind=kind)


def _fa_pallas(q, k, v, bias3, *, causal, scale, block_q, block_k,
               interpret, window=None):
    """Forward pallas_call on PADDED folded shapes. q [BH, Tq, D],
    k [BH, Tk, D], v [BH, Tk, Dv], bias3 None or [BH, 1, Tk]; Tq/Tk block
    multiples. The output has v's depth. With a ``window`` the inner grid
    axis is the key tiles of a query tile's band (``_key_band``), through
    the index maps."""
    BH, Tq, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    groups = _fwd_groups(block_q, nk)
    lane_sums = block_k % _LANE == 0
    # Trace-time: fires once per traced program, never per step.
    _telemetry.inc("hvd_flash_fwd_kernels_total", groups=str(groups),
                   denominator="lanes" if lane_sums else "rows")
    steps, k_tile = _key_steps(nq, nk, block_q, block_k, window)
    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             bq=block_q, bk=block_k, nk=nk,
                             has_bias=bias3 is not None, groups=groups,
                             lane_sums=lane_sums, window=window, steps=steps)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, k_tile(i, j), 0)),
        pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, k_tile(i, j), 0)),
    ]
    operands = [q, k, v]
    if bias3 is not None:
        operands.append(bias3)
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, i, j: (b, 0, k_tile(i, j))))
    return pl.pallas_call(
        kern,
        grid=(BH, nq, steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_FWD if window is None else KERNEL_FWD_WINDOW,
    )(*operands)


def _pad_bias3(bias, BH, Tk0, Tk):
    """Build the [BH, 1, Tk] additive-bias operand, or None when there is
    neither a mask nor key padding. Padded key columns get NEG_INF here —
    as DATA, not a kernel constant, so the kernels never capture a
    sequence-length scalar (interpret-mode custom_partitioning
    closure-converts captured constants into tracers)."""
    if bias is None:
        if Tk == Tk0:
            return None
        bias = jnp.zeros((BH, Tk0), jnp.float32)
    pad = Tk - bias.shape[1]
    if pad:
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=NEG_INF)
    return bias[:, None, :]


def _partition_enabled() -> bool:
    """Whether to wrap the pallas calls in ``custom_partitioning`` so they
    compose with GSPMD/jit sharding (batch*head dim partitioned, sequence
    and depth replicated). On by default on TPU; CPU meshes opt in via
    ``HOROVOD_FLASH_PARTITION=1`` (tests use this with interpret mode)."""
    import os
    env = os.environ.get("HOROVOD_FLASH_PARTITION")
    if env is not None:
        return env not in ("0", "false", "False", "")
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _sharded_wrapper(kind: str, has_bias: bool):
    """Build the ``custom_partitioning`` wrapper for ``kind`` in
    {"fwd", "bwd"}: dim 0 (batch*head) is partitioned, sequence/depth are
    replicated. Static config travels as ``static_argnums`` —
    custom_partitioning closure-converts, so closed-over ints feeding jax
    ops would come back as tracers. One wrapper per ``has_bias`` so the
    no-mask path never materialises a zeros bias.

    The Shardy rule declares need-replication factors; the ``partition``
    callback additionally FORCES dim-1/2 replication on its returned
    shardings so the legacy (non-Shardy) GSPMD path reshards rather than
    running the kernel on silently-wrong local sequence blocks."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec

    n_arrays = (3 if kind == "fwd" else 7) + int(has_bias)
    bias_term = ", b s k" if has_bias else ""
    if kind == "fwd":
        rule = f"b q d, b k d, b k e{bias_term} -> b q e, b q s, b q s"
    else:
        rule = (f"b q d, b k d, b k e, b q e, b q s, b q s, b q s"
                f"{bias_term} -> b q d, b k d, b k e")

    def run(arrays, causal, scale, block_q, block_k, interpret, window):
        bias3 = arrays[-1] if has_bias else None
        core = arrays[:n_arrays - 1] if has_bias else arrays
        if kind == "fwd":
            return _fa_pallas(*core, bias3, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret, window=window)
        return _fa_bwd_pallas(*core, bias3, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret, window=window)

    def impl(*args):
        return run(args[:n_arrays], *args[n_arrays:])

    wrapped = custom_partitioning(
        impl, static_argnums=tuple(range(n_arrays, n_arrays + 6)))

    def _dim0(sharding):
        spec = sharding.spec
        return spec[0] if len(spec) else None

    def partition(causal, scale, block_q, block_k, interpret, window,
                  mesh, arg_shapes, result_shape):
        arg_shardings = tuple(
            NamedSharding(mesh, PartitionSpec(_dim0(a.sharding), None, None))
            for a in arg_shapes)
        out_shardings = tuple(
            NamedSharding(mesh, PartitionSpec(_dim0(r.sharding), None, None))
            for r in result_shape)

        def lower(*arrays):
            return run(arrays, causal, scale, block_q, block_k, interpret,
                       window)

        return mesh, lower, out_shardings, arg_shardings

    def infer(causal, scale, block_q, block_k, interpret, window,
              mesh, arg_shapes, shape):
        sh = NamedSharding(
            mesh, PartitionSpec(_dim0(arg_shapes[0].sharding), None, None))
        return (sh, sh, sh)

    wrapped.def_partition(
        partition=partition,
        infer_sharding_from_operands=infer,
        sharding_rule=rule,
        need_replication_factors=("q", "d", "k", "e", "s"))
    return wrapped


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "partition",
    "window"))
def _fa_call(q, k, v, bias=None, *, causal, scale, block_q, block_k,
             interpret, partition, window=None):
    """q [BH, Tq, D], k [BH, Tk, D], v [BH, Tk, Dv], optional additive
    score bias [BH, Tk] → (o [BH, Tq, Dv], m, l [BH, Tq])."""
    BH = q.shape[0]
    q, Tq0 = _pad_axis(q, 1, block_q)
    k, Tk0 = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    Tq, Tk = q.shape[1], k.shape[1]
    bias3 = _pad_bias3(bias, BH, Tk0, Tk)
    if partition:
        w = _sharded_wrapper("fwd", bias3 is not None)
        args = (q, k, v) + ((bias3,) if bias3 is not None else ())
        o, m, l = w(*args, causal, scale, block_q, block_k, interpret,
                    window)
    else:
        o, m, l = _fa_pallas(q, k, v, bias3, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, window=window)
    return o[:, :Tq0], m[:, :Tq0, 0], l[:, :Tq0, 0]


def _recompute_p_ds(q, k, v, do, m, l, dsum, bias_tile, *, scale, causal,
                    bq, bk, iq, ik, window=None):
    """Shared backward-tile recompute: probability tile ``p`` and score
    cotangent ``ds`` for one (q-block, k-block) pair, from the saved softmax
    stats. Masking must mirror ``_fa_kernel`` exactly."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    k_pos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if bias_tile is not None:
        s = s + bias_tile.astype(jnp.float32)
    if causal:
        q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(_seen(q_pos, k_pos, window), s, NEG_INF)
    # 1/l once a row ([bq, 1]), multiplied in: a divide of the whole tile
    # costs several VPU passes over [bq, bk] for the same quotient.
    inv_l = 1.0 / jnp.where(l == 0.0, 1.0, l)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m)) * inv_l
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - dsum)  # dsum: rowsum(dO*O), the FA2 correction term
    return p, ds


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
                      *refs, scale, causal, bq, bk, nk, has_bias,
                      window=None, steps=None):
    """dQ pass: grid (BH, q-block, k-block), k innermost; recomputes the
    probability tile from the saved (m, l) softmax stats (FlashAttention-2
    backward), folds dS·K into a per-q-block accumulator."""
    if has_bias:
        bias_ref, dq_ref, acc = refs
    else:
        dq_ref, acc = refs
    iq = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    if window is None:
        ik, last = step, nk - 1
        visible = ((iq + 1) * bq - 1 >= ik * bk) if causal else (ik >= 0)
    else:
        ik, visible = _band_step(
            iq, step, lambda i: _key_band(i, bq, bk, nk, window))
        last = steps - 1

    @pl.when(visible)
    def _compute():
        k = k_ref[0]
        _, ds = _recompute_p_ds(
            q_ref[0], k, v_ref[0], do_ref[0], m_ref[0], l_ref[0], d_ref[0],
            bias_ref[0] if has_bias else None, scale=scale, causal=causal,
            bq=bq, bk=bk, iq=iq, ik=ik, window=window)
        acc[:] = acc[:] + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(step == last)
    def _emit():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
                       *refs, scale, causal, bq, bk, nq, has_bias,
                       window=None, steps=None):
    """dK/dV pass: grid (BH, k-block, q-block), q innermost. Padded q rows
    contribute nothing because their dO (and rowsum term) are zero-padded."""
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    ikb = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if window is None:
        iqb, last = step, nq - 1
        visible = ((iqb + 1) * bq - 1 >= ikb * bk) if causal else (iqb >= 0)
    else:
        iqb, visible = _band_step(
            ikb, step, lambda i: _query_band(i, bq, bk, nq, window))
        last = steps - 1

    @pl.when(visible)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k_ref[0], v_ref[0], do, m_ref[0], l_ref[0], d_ref[0],
            bias_ref[0] if has_bias else None, scale=scale, causal=causal,
            bq=bq, bk=bk, iq=iqb, ik=ikb, window=window)
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(step == last)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
                         *refs, scale, causal, bq, bk, has_bias,
                         window=None):
    """Whole backward of one (batch*head) whose sequence is ONE tile in both
    directions: nothing accumulates across grid steps, so ``p`` and ``ds``
    are computed once and dQ, dK, dV leave straight from them."""
    if has_bias:
        bias_ref, dq_ref, dk_ref, dv_ref = refs
    else:
        dq_ref, dk_ref, dv_ref = refs
    q = q_ref[0]
    k = k_ref[0]
    do = do_ref[0]
    p, ds = _recompute_p_ds(
        q, k, v_ref[0], do, m_ref[0], l_ref[0], d_ref[0],
        bias_ref[0] if has_bias else None, scale=scale, causal=causal,
        bq=bq, bk=bk, iq=0, ik=0, window=window)
    dv_ref[0] = lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    ds = ds.astype(q.dtype)
    dq_ref[0] = (lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale).astype(dq_ref.dtype)
    dk_ref[0] = (lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale).astype(dk_ref.dtype)


def _fa_bwd_tiled_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, d_ref,
                         *refs, scale, causal, bq, bk, nq, nk, has_bias,
                         window=None, steps=None):
    """Whole backward of a sequence of several tiles: grid (BH, k-block,
    q-block), q innermost as in the dK/dV pass, and ``p`` and ``ds`` computed
    ONCE a visible tile. dK and dV accumulate over the inner axis as there;
    dQ accumulates over the OUTER axis into ``dq_acc``, a float32 scratch
    that holds this (batch*head)'s whole [Tq, D] row, so each query tile
    meets its key tiles in ascending order as in the dQ pass. ``dq_ref`` is
    that whole row too (its block index moves with the batch*head alone): it
    is written tile by tile during the last key block and leaves VMEM once
    a (batch*head). With a ``window`` the inner axis is the query tiles of
    the key tile's band, and a query tile's dQ rows are zeroed in the first
    key tile of ITS band and written in the last: the key tiles still come
    in ascending order, so no sum changes its order."""
    if has_bias:
        bias_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    ikb = pl.program_id(1)
    step = pl.program_id(2)
    banded = window is not None
    if banded:
        iqb, inside = _band_step(
            ikb, step, lambda i: _query_band(i, bq, bk, nq, window))
        k_first, k_last = _key_band(iqb, bq, bk, nk, window)
    else:
        iqb = step
    rows = pl.ds(pl.multiple_of(iqb * bq, bq), bq)

    @pl.when(step == 0)
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(inside & (ikb == k_first) if banded else ikb == 0)
    def _init_q():
        dq_acc[rows, :] = jnp.zeros((bq, dq_acc.shape[1]), dq_acc.dtype)

    if banded:
        visible = inside
    else:
        visible = ((iqb + 1) * bq - 1 >= ikb * bk) if causal else (iqb >= 0)

    @pl.when(visible)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k, v_ref[0], do, m_ref[0], l_ref[0], d_ref[0],
            bias_ref[0] if has_bias else None, scale=scale, causal=causal,
            bq=bq, bk=bk, iq=iqb, ik=ikb, window=window)
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = ds.astype(q.dtype)
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dq_acc[rows, :] = dq_acc[rows, :] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(step == (steps if banded else nq) - 1)
    def _emit_kv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(inside & (ikb == k_last) if banded else ikb == nk - 1)
    def _emit_q():
        dq_ref[0, rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _tiled_bwd_vmem_bytes(Tq, D, Dv, block_q, block_k, itemsize, has_bias):
    """VMEM the several-tile fused backward holds, summed from its shapes:
    every operand and result block twice (the pipeline's two buffers), the
    three accumulators, and the [block_q, block_k] temporaries of one tile.
    A trailing dim fills whole 128-lane tiles (192 deep takes 256; a
    ``(block_q, 1)`` statistic 128)."""
    lanes = lambda n: -(-n // _LANE) * _LANE
    d, dv = lanes(D), lanes(Dv)
    blocks = itemsize * (block_q + block_k) * (d + dv)   # q, dO; k, v
    blocks += 3 * 4 * block_q * _LANE                    # m, l, dsum
    if has_bias:
        blocks += 4 * 8 * lanes(block_k)
    blocks += itemsize * (block_k * (d + dv) + Tq * d)   # dk, dv; dq's row
    acc = 4 * (block_k * (d + dv) + Tq * d)
    # s -> p and dp -> ds in float32 with the operand-dtype copies of p and
    # ds: four float32 tiles bound them (Mosaic keeps under three at 512).
    tile = 4 * 4 * block_q * lanes(block_k)
    return 2 * blocks + acc + tile


def _kv_major_specs(D, Dv, block_q, block_k, has_bias,
                    q_of=lambda i, j: j):
    """Block specs on grid (BH, k-block, q-block), q INNERMOST (the dK/dV
    pass's and the fused tiled kernel's): the operands' in order, then dK's
    and dV's. ``q_of(i, j)`` is the query tile of inner step ``j`` of key
    tile ``i``: the step itself, or the tile of the key tile's band."""
    q_tile = lambda b, i, j: (b, q_of(i, j), 0)
    k_tile = lambda b, i, j: (b, i, 0)
    dk_spec = pl.BlockSpec((1, block_k, D), k_tile)
    dv_spec = pl.BlockSpec((1, block_k, Dv), k_tile)
    q_stat = pl.BlockSpec((1, block_q, 1), q_tile)
    in_specs = [
        pl.BlockSpec((1, block_q, D), q_tile),    # q
        dk_spec, dv_spec,                         # k, v
        pl.BlockSpec((1, block_q, Dv), q_tile),   # do
        q_stat, q_stat, q_stat,                   # m, l, dsum
    ]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, i)))
    return in_specs, [dk_spec, dv_spec]


def _fa_bwd_tiled(q, k, v, do, m3, l3, dsum, bias3, *, causal, scale,
                  block_q, block_k, vmem_bytes, interpret, window=None):
    """The several-tile fused backward: one pallas_call on grid
    (BH, nk, nq), or with a ``window`` (BH, nk, query tiles of a band)."""
    BH, Tq, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    has_bias = bias3 is not None
    steps, q_of = _query_steps(nq, nk, block_q, block_k, window)
    in_specs, dkv_specs = _kv_major_specs(D, Dv, block_q, block_k, has_bias,
                                          q_of)
    operands = [q, k, v, do, m3, l3, dsum] + ([bias3] if has_bias else [])
    params = None
    if vmem_bytes > _VMEM_SCOPED_DEFAULT:
        params = pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes)
    return tuple(pl.pallas_call(
        functools.partial(_fa_bwd_tiled_kernel, scale=scale, causal=causal,
                          bq=block_q, bk=block_k, nq=nq, nk=nk,
                          has_bias=has_bias, window=window, steps=steps),
        grid=(BH, nk, steps),
        in_specs=in_specs,
        # dQ's block is the (batch*head)'s whole row
        out_specs=[pl.BlockSpec((1, Tq, D), lambda b, i, j: (b, 0, 0)),
                   *dkv_specs],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((Tq, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name=_bwd_name(_BWD_TILED, window),
    )(*operands))


def _bwd_name(name, window):
    """A backward kernel's name: its windowed twin's under a window."""
    if window is None:
        return name
    return KERNEL_BWD_WINDOW[KERNEL_BWD.index(name)]


def _fa_bwd_fused(q, k, v, do, m3, l3, dsum, bias3, *, causal, scale,
                  interpret, window=None):
    """The single-tile backward: one pallas_call on grid (BH,), every
    operand's whole (batch*head) row as its block."""
    BH, Tq, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    row = lambda b: (b, 0, 0)
    q_rows = pl.BlockSpec((1, Tq, D), row)       # q, dq
    k_rows = pl.BlockSpec((1, Tk, D), row)       # k, dk
    v_rows = pl.BlockSpec((1, Tk, Dv), row)      # v, dv
    do_rows = pl.BlockSpec((1, Tq, Dv), row)     # do
    q_stat = pl.BlockSpec((1, Tq, 1), row)       # m, l, dsum
    operands = [q, k, v, do, m3, l3, dsum]
    in_specs = [q_rows, k_rows, v_rows, do_rows, q_stat, q_stat, q_stat]
    if bias3 is not None:
        operands.append(bias3)
        in_specs.append(pl.BlockSpec((1, 1, Tk), row))
    return tuple(pl.pallas_call(
        functools.partial(_fa_bwd_fused_kernel, scale=scale, causal=causal,
                          bq=Tq, bk=Tk, has_bias=bias3 is not None,
                          window=window),
        grid=(BH,),
        in_specs=in_specs,
        out_specs=[q_rows, k_rows, v_rows],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, Dv), v.dtype),
        ],
        interpret=interpret,
        name=_bwd_name(_BWD_FUSED, window),
    )(*operands))


# ``hvd_flash_window_kernels_total``'s ``kind`` of a backward form
_WINDOW_BWD_KINDS = {"fused": "bwd_fused", "fused_tiled": "bwd_tiled",
                     "two_pass": "bwd_two_pass"}


def _bwd_kind(nq, nk, vmem_bytes):
    """The backward's form for a padded shape (module docstring)."""
    if nq == 1 and nk == 1:
        return "fused"
    return "fused_tiled" if vmem_bytes <= _VMEM_BYTES else "two_pass"


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _fa_bwd_pallas(q, k, v, do, m3, l3, dsum, bias3, *, causal, scale,
                   block_q, block_k, interpret, window=None):
    """Backward pallas_calls on PADDED folded shapes → (dq, dk, dv): the
    fused kernel when the sequence is one tile in both directions; with
    several tiles the fused tiled kernel while a (batch*head)'s dQ row fits
    ``_VMEM_BYTES`` beside the tiles, the dQ and dK/dV passes beyond. A
    ``window`` chooses among the same three, under their windowed names and
    with the several-tile forms' inner axes cut to the band."""
    BH, Tq, D = q.shape
    Tk, Dv = k.shape[1], v.shape[2]
    nq, nk = Tq // block_q, Tk // block_k
    vmem_bytes = _tiled_bwd_vmem_bytes(Tq, D, Dv, block_q, block_k,
                                       q.dtype.itemsize, bias3 is not None)
    kind = _bwd_kind(nq, nk, vmem_bytes)
    # Trace-time: fires once per traced program, never per step.
    _telemetry.inc("hvd_flash_bwd_kernels_total", kind=kind)
    if kind == "fused":
        return _fa_bwd_fused(q, k, v, do, m3, l3, dsum, bias3, causal=causal,
                             scale=scale, interpret=interpret, window=window)
    if kind == "fused_tiled":
        return _fa_bwd_tiled(q, k, v, do, m3, l3, dsum, bias3, causal=causal,
                             scale=scale, block_q=block_q, block_k=block_k,
                             vmem_bytes=vmem_bytes, interpret=interpret,
                             window=window)

    k_steps, k_tile = _key_steps(nq, nk, block_q, block_k, window)
    base_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),   # q
        pl.BlockSpec((1, block_k, D),
                     lambda b, i, j: (b, k_tile(i, j), 0)),         # k
        pl.BlockSpec((1, block_k, Dv),
                     lambda b, i, j: (b, k_tile(i, j), 0)),         # v
        pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),  # do
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),   # m
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),   # l
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),   # dsum
    ]
    operands = [q, k, v, do, m3, l3, dsum]
    if bias3 is not None:
        operands.append(bias3)
        base_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, i, j: (b, 0, k_tile(i, j))))

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=block_q, bk=block_k, nk=nk,
                          has_bias=bias3 is not None, window=window,
                          steps=k_steps),
        grid=(BH, nq, k_steps),
        in_specs=base_specs,
        out_specs=[pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name=_bwd_name(_BWD_DQ, window),
    )(*operands)[0]

    # dK/dV pass iterates q INNERMOST: the grid indices swap meaning (i = k
    # block, j = q block).
    q_steps, q_of = _query_steps(nq, nk, block_q, block_k, window)
    kv_specs, dkv_specs = _kv_major_specs(D, Dv, block_q, block_k,
                                          bias3 is not None, q_of)
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=block_q, bk=block_k, nq=nq,
                          has_bias=bias3 is not None, window=window,
                          steps=q_steps),
        grid=(BH, nk, q_steps),
        in_specs=kv_specs,
        out_specs=dkv_specs,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        name=_bwd_name(_BWD_DKV, window),
    )(*operands)
    return dq, dk, dv


def _fa_bwd_call(q, k, v, do, o, m, l, bias=None, *, causal, scale,
                 block_q, block_k, interpret, partition, window=None):
    """Folded-[BH] backward. Returns (dq, dk, dv) in the input dtypes."""
    BH, Tq0, _ = q.shape
    dsum = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                   keepdims=True)  # [BH, Tq, 1] — the FA2 rowsum(dO*O) term
    q, _ = _pad_axis(q, 1, block_q)
    do, _ = _pad_axis(do, 1, block_q)
    dsum, _ = _pad_axis(dsum, 1, block_q)
    m3, _ = _pad_axis(m[..., None].astype(jnp.float32), 1, block_q)
    l3, _ = _pad_axis(l[..., None].astype(jnp.float32), 1, block_q)
    k, Tk0 = _pad_axis(k, 1, block_k)
    v, _ = _pad_axis(v, 1, block_k)
    Tq, Tk = q.shape[1], k.shape[1]
    bias3 = _pad_bias3(bias, BH, Tk0, Tk)
    if window is not None:
        kind = _bwd_kind(Tq // block_q, Tk // block_k, _tiled_bwd_vmem_bytes(
            Tq, q.shape[2], v.shape[2], block_q, block_k, q.dtype.itemsize,
            bias3 is not None))
        _count_window_call(_WINDOW_BWD_KINDS[kind], window, Tq, Tk, block_q,
                           block_k)
    if partition:
        w = _sharded_wrapper("bwd", bias3 is not None)
        args = (q, k, v, do, m3, l3, dsum) + (
            (bias3,) if bias3 is not None else ())
        dq, dk, dv = w(*args, causal, scale, block_q, block_k, interpret,
                       window)
    else:
        dq, dk, dv = _fa_bwd_pallas(q, k, v, do, m3, l3, dsum, bias3,
                                    causal=causal, scale=scale,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret, window=window)
    return dq[:, :Tq0], dk[:, :Tk0], dv[:, :Tk0]


def _reference_partial(q, k, v, bias=None, *, causal, scale, window=None):
    """Blockless jnp oracle with the same (o, m, l) partial semantics.

    Used as the recompute path of the backward pass and by the test suite.
    q [B, Tq, H, D]; k [B, Tk, H, D]; v [B, Tk, H, Dv]; optional additive
    score bias [B, Tk]; returns o [B,Tq,H,Dv], m/l [B,H,Tq].
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)[:, None, None, :]
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        mask = _seen(jnp.arange(Tq)[:, None], jnp.arange(Tk)[None, :],
                     window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m[..., None]))
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o = o / jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype), m, l


def _fold(x, B, H):
    return x.transpose(0, 2, 1, 3).reshape(B * H, -1, x.shape[-1])


def _fold_bias(bias, B, H, Tk):
    # [B, Tk] → [BH, Tk] to match the folded batch*head leading dim.
    return jnp.broadcast_to(bias[:, None, :], (B, H, Tk)).reshape(B * H, Tk)


def _fa_fwd_impl(q, k, v, bias, causal, scale, block_q, block_k, window):
    """Plain (non-vjp) forward shared by both custom_vjp cores."""
    from jax.ad_checkpoint import checkpoint_name
    interpret = _use_interpret()
    B, Tq, H, _ = q.shape
    Tk = k.shape[1]
    fbias = None if bias is None else _fold_bias(bias, B, H, Tk)
    if window is not None:
        _count_window_call("fwd", window, Tq, Tk, block_q, block_k)
    o, m, l = _fa_call(_fold(q, B, H), _fold(k, B, H),
                       _fold(v, B, H), fbias, causal=causal,
                       scale=scale, block_q=block_q, block_k=block_k,
                       interpret=interpret,
                       partition=_partition_enabled(), window=window)
    o = o.reshape(B, H, Tq, -1).transpose(0, 2, 1, 3)
    # Named so a remat policy can SAVE the kernel's outputs — they are
    # exactly the custom-vjp residuals (o, m, l), so a policy that keeps
    # them (models' "dots_attn") skips the whole fwd-kernel re-run inside
    # the backward of a remat block, at [B,T,H,D] + 2x[B,H,T] per layer.
    return (checkpoint_name(o, "attn_out"),
            checkpoint_name(m.reshape(B, H, Tq), "attn_lse_m"),
            checkpoint_name(l.reshape(B, H, Tq), "attn_lse_l"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fa_core(q, k, v, bias, causal, scale, block_q, block_k, window):
    return _fa_fwd_impl(q, k, v, bias, causal, scale, block_q, block_k, window)


def _fa_fwd(q, k, v, bias, causal, scale, block_q, block_k, window):
    out = _fa_core(q, k, v, bias, causal, scale, block_q, block_k, window)
    return out, (q, k, v, bias)


def _fa_bwd(causal, scale, block_q, block_k, window, res, cts):
    q, k, v, bias = res
    do, dm, dl = cts
    # The m/l residuals carry real cotangents when the caller merges partials
    # (ring attention weights each partial by exp(m_i - m) * l_i), so the
    # recompute must differentiate through all three outputs.

    def recompute(q, k, v, bias):
        return _reference_partial(q, k, v, bias, causal=causal, scale=scale,
                                  window=window)

    _, vjp = jax.vjp(recompute, q, k, v, bias)
    return vjp((do.astype(q.dtype), dm.astype(jnp.float32),
                dl.astype(jnp.float32)))


_fa_core.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fa_core_nores(q, k, v, bias, causal, scale, block_q, block_k, window):
    """Output-only core used when the caller does not need (m, l): its
    backward runs the dedicated blockwise Pallas kernels instead of the
    materialised-softmax recompute, keeping the [Tq, Tk] matrix out of HBM
    in BOTH passes. ``bias`` receives a zero cotangent — it only ever
    derives from a (constant) kv padding mask on this path."""
    return _fa_fwd_impl(q, k, v, bias, causal, scale, block_q, block_k,
                        window)[0]


def _fa_fwd_nores(q, k, v, bias, causal, scale, block_q, block_k, window):
    o, m, l = _fa_fwd_impl(q, k, v, bias, causal, scale, block_q, block_k,
                           window)
    return o, (q, k, v, bias, o, m, l)


def _fa_bwd_nores(causal, scale, block_q, block_k, window, res, do):
    q, k, v, bias, o, m, l = res
    B, Tq, H, _ = q.shape
    Tk = k.shape[1]
    fbias = None if bias is None else _fold_bias(bias, B, H, Tk)
    fm = m.reshape(B * H, Tq)
    fl = l.reshape(B * H, Tq)
    dq, dk, dv = _fa_bwd_call(
        _fold(q, B, H), _fold(k, B, H), _fold(v, B, H),
        _fold(do, B, H), _fold(o, B, H), fm, fl, fbias,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=_use_interpret(), partition=_partition_enabled(),
        window=window)
    unfold = lambda x, T: x.reshape(B, H, T, -1).transpose(0, 2, 1, 3)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return unfold(dq, Tq), unfold(dk, Tk), unfold(dv, Tk), dbias


_fa_core_nores.defvjp(_fa_fwd_nores, _fa_bwd_nores)


def flash_attention(q, k, v, *, causal: bool = True,
                    kv_mask=None,
                    scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    return_residuals: bool = False,
                    window: Optional[int] = None):
    """Blockwise (flash) attention on q, k [B, T, H, D] and v [B, Tk, H, Dv].

    ``v`` may have another depth than ``q`` and ``k`` (latent attention:
    192-deep queries and keys, 128-deep values); the output has ``v``'s
    depth and the default ``scale`` is ``1/sqrt(D)`` of the query's.

    ``kv_mask`` is an optional [B, Tk] bool array marking real (attendable)
    keys — the BERT-style padding mask; masked keys never win the softmax.

    ``window``, with ``causal`` and queries and keys of one sequence
    (``Tq == Tk``), is a static count of visible keys a query: key ``j`` is
    visible to query ``i`` iff ``i - window < j <= i``, the query's own
    among them (sliding-window attention). The grid of a windowed call
    covers the band alone: the forward's inner axis is the key tiles of a
    query tile's band, the backward's the query tiles of a key tile's, so
    no grid step is paid for a tile outside it, and the kernels carry the
    names ``KERNEL_FWD_WINDOW`` / ``KERNEL_BWD_WINDOW``. A window no
    shorter than the sequence is no window: that call is the unwindowed
    one, bit for bit.

    Returns the attention output, plus ``(m, l)`` softmax residuals of shape
    [B, H, Tq] when ``return_residuals`` — feed those to
    :func:`merge_partials` to combine attention over disjoint key shards
    (ring attention's per-step merge).

    Block defaults were swept on v5e (T=4096 causal fwd+bwd, interleaved
    A/B): 512 beats 128 by ~4x (grid overhead) and the materialised-softmax
    path by ~5x at D=64 / ~10x at D=128; blocks are clamped to the padded
    sequence length so short inputs still work.
    """
    D = q.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bias = None
    if kv_mask is not None:
        bias = jnp.where(kv_mask, 0.0, NEG_INF).astype(jnp.float32)
    if window is not None:
        if not causal or q.shape[1] != k.shape[1] or window < 1:
            raise ValueError(
                "flash_attention: a window is a positive count of visible "
                "keys under causal=True with Tq == Tk; got "
                f"window={window}, causal={causal}, Tq={q.shape[1]}, "
                f"Tk={k.shape[1]}")
        window = int(window) if window < k.shape[1] else None
    # Clamp to the sequence length rounded UP to a multiple of 8: block
    # sublane dims must stay 8-divisible for the TPU tiling rule (padding
    # covers the remainder).
    block_q = min(block_q, -(-max(q.shape[1], 1) // 8) * 8)
    block_k = min(block_k, -(-max(k.shape[1], 1) // 8) * 8)
    if return_residuals:
        o, m, l = _fa_core(q, k, v, bias, causal, float(scale), block_q,
                           block_k, window)
        return o, (m, l)
    # No residuals requested → the blockwise backward kernels apply (the
    # recompute-vjp core is only needed when (m, l) carry cotangents).
    return _fa_core_nores(q, k, v, bias, causal, float(scale), block_q,
                          block_k, window)


def merge_partials(p1: Tuple, p2: Tuple) -> Tuple:
    """Exactly combine two attention partials over disjoint key sets.

    Each partial is ``(o [B,T,H,D], m [B,H,T], l [B,H,T])`` with ``o``
    normalised by its own ``l`` (a partial that saw zero keys has l == 0 and
    contributes nothing). Returns the combined partial in the same form —
    associative and commutative, so ring steps can fold in any order.
    """
    o1, m1, l1 = p1
    o2, m2, l2 = p2
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(jnp.maximum(m1 - m, NEG_INF)) * l1
    a2 = jnp.exp(jnp.maximum(m2 - m, NEG_INF)) * l2
    l = a1 + a2
    den = jnp.where(l == 0.0, 1.0, l)
    w1 = (a1 / den).transpose(0, 2, 1)[..., None]
    w2 = (a2 / den).transpose(0, 2, 1)[..., None]
    o = o1.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2
    return o.astype(o1.dtype), m, l
