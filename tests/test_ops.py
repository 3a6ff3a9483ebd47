"""Pallas kernel tests (interpret mode on the CPU test mesh).

Mirrors the reference's approach of testing device code end-to-end through
the public API against a plain oracle (SURVEY.md §4: no C++ unit tests —
behavior is pinned via Python-level parity checks)."""

import contextlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import (flash_attention, fused_combine, fused_norms_dot,
                             merge_partials)
from horovod_tpu.ops.flash_attention import _reference_partial

# ``horovod_tpu.ops`` re-exports the function under the module's name.
fa = importlib.import_module("horovod_tpu.ops.flash_attention")

# The backward's kernels by the form ``_fa_bwd_pallas`` picks for a shape.
BWD_KERNELS = {"fused": ["hvd_flash_bwd"],
               "fused_tiled": ["hvd_flash_bwd_tiled"],
               "two_pass": ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]}


@contextlib.contextmanager
def forced_two_pass():
    """The dQ and dK/dV passes for a shape whose dQ row fits VMEM: no option
    selects them, so the module's VMEM constant is patched to nothing. jit
    keeps a trace per shape, hence the cleared caches on the way in and
    out."""
    patch = pytest.MonkeyPatch()
    patch.setattr(fa, "_VMEM_BYTES", 0)
    jax.clear_caches()
    try:
        yield
    finally:
        patch.undo()
        jax.clear_caches()


@pytest.fixture(params=["fused_tiled", "two_pass"])
def bwd_form(request):
    """Both several-tile forms of the backward, the second one forced."""
    with (forced_two_pass() if request.param == "two_pass"
          else contextlib.nullcontext()):
        yield request.param


def _rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T", [64, 100])
def test_flash_matches_reference(causal, T):
    B, H, D = 2, 2, 32
    q = _rand((B, T, H, D), 0)
    k = _rand((B, T, H, D), 1)
    v = _rand((B, T, H, D), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref, _, _ = _reference_partial(q, k, v, causal=causal, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_unequal_lengths():
    B, H, D = 1, 2, 32
    q = _rand((B, 48, H, D), 3)
    k = _rand((B, 80, H, D), 4)
    v = _rand((B, 80, H, D), 5)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref, _, _ = _reference_partial(q, k, v, causal=False, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_close():
    B, T, H, D = 1, 64, 2, 32
    q = _rand((B, T, H, D), 6, jnp.bfloat16)
    k = _rand((B, T, H, D), 7, jnp.bfloat16)
    v = _rand((B, T, H, D), 8, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref, _, _ = _reference_partial(q, k, v, causal=True, scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_merge_partials_equals_full_attention():
    """Attention over the full key set == merge of partials over key shards
    — the exact property ring attention relies on each ppermute step."""
    B, T, H, D = 2, 64, 2, 32
    q = _rand((B, T, H, D), 10)
    k = _rand((B, T, H, D), 11)
    v = _rand((B, T, H, D), 12)
    full, _ = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                              return_residuals=True), None
    full = full[0]
    half = T // 2
    p1 = flash_attention(q, k[:, :half], v[:, :half], causal=False,
                         block_q=32, block_k=32, return_residuals=True)
    p2 = flash_attention(q, k[:, half:], v[:, half:], causal=False,
                         block_q=32, block_k=32, return_residuals=True)
    o, (m, l) = p1[0], p1[1]
    o2, (m2, l2) = p2[0], p2[1]
    merged, _, _ = merge_partials((o, m, l), (o2, m2, l2))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_merge_partials_of_512_key_tiles_equals_full_attention():
    """Ring attention's contract at the tile the models run: with key tiles
    of whole 128-lane tiles the kernel carries the denominator as per-lane
    partial sums, and the ``l`` it returns must still be the whole row's
    sum: two halves of the keys, one tile each, merge into the attention
    over both."""
    B, T, H, D = 1, 1024, 2, 64
    q, k, v = (_rand((B, T, H, D), seed) for seed in (20, 21, 22))
    mask = jnp.arange(T)[None, :] < T - 9
    full, (m, l) = flash_attention(q, k, v, causal=False, kv_mask=mask,
                                   return_residuals=True)
    half = T // 2
    parts = []
    for keys in (slice(0, half), slice(half, T)):
        o_i, (m_i, l_i) = flash_attention(
            q, k[:, keys], v[:, keys], causal=False, kv_mask=mask[:, keys],
            return_residuals=True)
        parts.append((o_i, m_i, l_i))
    merged, m2, l2 = merge_partials(*parts)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(m2), np.asarray(m))
    np.testing.assert_allclose(np.asarray(l2), np.asarray(l), rtol=2e-6)


def test_flash_gradients_match_reference():
    B, T, H, D = 1, 32, 2, 16
    q = _rand((B, T, H, D), 20)
    k = _rand((B, T, H, D), 21)
    v = _rand((B, T, H, D), 22)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        o, _, _ = _reference_partial(q, k, v, causal=True, scale=D ** -0.5)
        return jnp.sum(o ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fused_norms_dot():
    a = _rand((1000,), 30)
    b = _rand((1000,), 31)
    dot, na, nb = fused_norms_dot(a, b)
    np.testing.assert_allclose(float(dot), float(jnp.vdot(a, b)), rtol=1e-5)
    np.testing.assert_allclose(float(na), float(jnp.vdot(a, a)), rtol=1e-5)
    np.testing.assert_allclose(float(nb), float(jnp.vdot(b, b)), rtol=1e-5)


def test_fused_combine_matches_adasum_combine():
    from horovod_tpu.collectives.adasum import _combine
    a = _rand((513, 7), 40)
    b = _rand((513, 7), 41)
    got = fused_combine(a, b)
    want = _combine(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_combine_zero_norm_degrades_to_sum():
    a = jnp.zeros((64,))
    b = _rand((64,), 42)
    got = fused_combine(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_pallas_impl_matches_local(causal):
    """The Pallas per-shard kernel + merge_partials ring must agree with the
    single-device oracle on the 8-device CPU mesh (interpret mode)."""
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from horovod_tpu.parallel import create_mesh, local_attention, \
        ring_attention

    rng = np.random.RandomState(5)
    B, T, H, D = 2, 32, 2, 8
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    ref = np.asarray(local_attention(q, k, v, causal=causal))
    mesh = create_mesh({"sp": 8})

    def body(qb, kb, vb):
        return ring_attention(qb, kb, vb, "sp", causal=causal, impl="pallas")

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                          out_specs=P(None, "sp"), check_vma=False))
    out = np.asarray(f(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_pallas_gradients_match_jnp_impl():
    """Gradients through the pallas ring path must match the jnp ring path —
    regression for the m/l residual cotangents being dropped in the VJP."""
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from horovod_tpu.parallel import create_mesh, ring_attention

    rng = np.random.RandomState(9)
    B, T, H, D = 1, 32, 2, 8
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    mesh = create_mesh({"sp": 8})

    def loss(impl):
        def body(qb, kb, vb):
            return ring_attention(qb, kb, vb, "sp", causal=True, impl=impl)
        f = shard_map(body, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                      out_specs=P(None, "sp"), check_vma=False)
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    g1 = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss("jnp"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_kv_mask_matches_reference():
    """Padding mask: masked keys never contribute; fully-masked rows are 0."""
    B, T, H, D = 2, 64, 2, 32
    q = _rand((B, T, H, D), 10)
    k = _rand((B, T, H, D), 11)
    v = _rand((B, T, H, D), 12)
    lengths = jnp.array([40, 64])
    mask = jnp.arange(T)[None, :] < lengths[:, None]
    out = flash_attention(q, k, v, causal=False, kv_mask=mask,
                          block_q=32, block_k=32)
    bias = jnp.where(mask, 0.0, -1e30)
    ref, _, _ = _reference_partial(q, k, v, bias, causal=False,
                                   scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # all-keys-masked batch row: output must be exactly zero, not NaN
    none = jnp.zeros((B, T), bool)
    out0 = flash_attention(q, k, v, causal=False, kv_mask=none,
                           block_q=32, block_k=32)
    assert not np.isnan(np.asarray(out0)).any()
    np.testing.assert_array_equal(np.asarray(out0), 0.0)


def test_flash_kv_mask_grads_flow():
    B, T, H, D = 1, 32, 2, 16
    q = _rand((B, T, H, D), 13)
    k = _rand((B, T, H, D), 14)
    v = _rand((B, T, H, D), 15)
    mask = jnp.arange(T)[None, :] < 20

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=False, kv_mask=mask,
                               block_q=16, block_k=16).sum()

    def ref_loss(q, k, v):
        bias = jnp.where(mask, 0.0, -1e30)
        o, _, _ = _reference_partial(q, k, v, bias, causal=False,
                                     scale=D ** -0.5)
        return o.sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_kernel_matches_reference(causal, bwd_form):
    """The dedicated blockwise backward (no-residual path) must match the
    materialised-softmax vjp, including causal tile skipping and padding in
    BOTH sequence dims (T=100/84 are not block multiples), as one fused
    tiled kernel and as the two passes."""
    B, H, D = 2, 2, 32
    Tq, Tk = (100, 100) if causal else (100, 84)
    q = _rand((B, Tq, H, D), 20)
    k = _rand((B, Tk, H, D), 21)
    v = _rand((B, Tk, H, D), 22)

    def loss(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=32,
                                block_k=32) ** 2).sum()

    def ref_loss(q, k, v):
        o, _, _ = _reference_partial(q, k, v, causal=causal,
                                     scale=D ** -0.5)
        return (o ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    assert _bwd_kernels(loss, q, k, v) == BWD_KERNELS[bwd_form]


def pallas_names(fn, *args):
    """Names of the ``pallas_call``s in ``fn``'s program, in program order
    (tests/test_spans.py uses this too)."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"] if "name" in eqn.params
                             else eqn.params["name_and_src_info"].name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _bwd_kernels(loss, *args):
    return sorted(n for n in pallas_names(
        jax.grad(loss, argnums=(0, 1, 2)), *args) if "bwd" in n)


_F32, _BF16 = (jnp.float32, 2e-4), (jnp.bfloat16, 5e-2)


@pytest.mark.parametrize("causal,Tq,Tk,masked,dtype_tol", [
    (True, 64, 64, False, _F32),
    (False, 64, 64, False, _F32),
    (False, 64, 64, True, _F32),
    (True, 64, 64, True, _F32),
    (False, 48, 80, False, _F32),       # cross attention, Tq != Tk
    (True, 100, 100, False, _F32),      # padded to 104 inside the one tile
    (False, 100, 84, True, _F32),       # both dims padded, and a mask
    (True, 64, 64, False, _BF16),
    (False, 100, 100, True, _BF16),
], ids=["causal", "full", "full_masked", "causal_masked", "cross",
        "causal_padded", "cross_padded_masked", "causal_bf16",
        "padded_masked_bf16"])
def test_flash_bwd_fused_kernel_matches_reference(causal, Tq, Tk, masked,
                                                  dtype_tol):
    """A sequence that is ONE tile in both directions (the default 512
    blocks clamp to it) takes the fused single-pass backward: same gradients
    as the materialised-softmax vjp."""
    dtype, tol = dtype_tol
    B, H, D = 2, 2, 32
    q = _rand((B, Tq, H, D), 40, dtype)
    k = _rand((B, Tk, H, D), 41, dtype)
    v = _rand((B, Tk, H, D), 42, dtype)
    mask = bias = None
    if masked:
        mask = jnp.arange(Tk)[None, :] < jnp.array([Tk - 24, Tk])[:, None]
        bias = jnp.where(mask, 0.0, -1e30)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, kv_mask=mask)
        return (o.astype(jnp.float32) ** 2).sum()

    def ref_loss(q, k, v):
        o, _, _ = _reference_partial(q, k, v, bias, causal=causal,
                                     scale=D ** -0.5)
        return (o.astype(jnp.float32) ** 2).sum()

    assert _bwd_kernels(loss, q, k, v) == ["hvd_flash_bwd"]
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_fused_agrees_with_two_pass(causal, bwd_form):
    """Same inputs through the fused kernel (one tile) and, at 32-token
    blocks, through the fused tiled kernel or the dQ and dK/dV passes: one
    algorithm, three grids."""
    B, H, D, Tq, Tk = 2, 2, 32, 100, 84
    q = _rand((B, Tq, H, D), 43)
    k = _rand((B, Tk, H, D), 44)
    v = _rand((B, Tk, H, D), 45)
    mask = jnp.arange(Tk)[None, :] < jnp.array([60, Tk])[:, None]

    def loss(q, k, v, **blocks):
        return (flash_attention(q, k, v, causal=causal, kv_mask=mask,
                                **blocks) ** 2).sum()

    def tiled(q, k, v):
        return loss(q, k, v, block_q=32, block_k=32)

    assert _bwd_kernels(loss, q, k, v) == ["hvd_flash_bwd"]
    assert _bwd_kernels(tiled, q, k, v) == BWD_KERNELS[bwd_form]
    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(tiled, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def check_tiled_equals_two_pass(T, block, D, Dv, causal, masked):
    """dQ, dK, dV of the fused tiled kernel and of the two passes: the same
    bits, and both the gradients of ``_reference_partial``. bf16 operands, as
    every cell feeds the kernels. (In float32 XLA:CPU, which runs the
    interpreted kernels here, fuses ``dot * scale - m`` differently in the
    dQ pass's program than in the dK/dV pass's, so those two already differ
    from each other by an ulp in one case, full and unmasked at 192/128.)"""
    B, H, dtype = 2, 2, jnp.bfloat16
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(T + D), 4)
    q = jax.random.normal(kq, (B, T, H, D), dtype)
    k = jax.random.normal(kk, (B, T, H, D), dtype)
    v = jax.random.normal(kv, (B, T, H, Dv), dtype)
    do = jax.random.normal(kd, (B, T, H, Dv), dtype)
    mask = bias = None
    if masked:
        mask = jnp.arange(T)[None, :] < jnp.array([T - 7, T])[:, None]
        bias = jnp.where(mask, 0.0, -1e30)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, kv_mask=mask,
                               block_q=block, block_k=block)

    def reference(q, k, v):
        return _reference_partial(q, k, v, bias, causal=causal,
                                  scale=D ** -0.5)[0]

    def grads():
        return jax.vjp(flash, q, k, v)[1](do)

    tiled = grads()
    assert _bwd_kernels(lambda *a: flash(*a).sum(), q, k, v) == \
        BWD_KERNELS["fused_tiled"]
    with forced_two_pass():
        two_pass = grads()
        assert _bwd_kernels(lambda *a: flash(*a).sum(), q, k, v) == \
            BWD_KERNELS["two_pass"]
    want = jax.vjp(reference, q, k, v)[1](do)
    for name, a, b, ref in zip("qkv", tiled, two_pass, want):
        assert a.dtype == dtype and a.shape == ref.shape, name
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), "d" + name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=5e-2, atol=5e-2, err_msg="d" + name)


@pytest.mark.parametrize("T,block", [(64, 16), (40, 16)],
                         ids=["four_tiles", "padded_to_48"])
@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_bwd_tiled_equals_two_pass_bit_for_bit(causal, masked, T,
                                                     block):
    """Several tiles and a dQ row that fits VMEM: ONE kernel computes p and
    ds once a tile and sums dQ over the key tiles in the order the dQ pass
    does, so nothing of the two passes' result changes."""
    check_tiled_equals_two_pass(T, block, 64, 64, causal, masked)


def _tiled_forward_as_before(q, k, v, bias3, *, causal, scale, block):
    """(o, m, l) as the forward kernel computed them up to PR 34, tile by
    tile in plain ``jnp`` on padded folded operands: a visit takes the whole
    tile's row max and row SUM and carries one denominator a row, where the
    kernel now carries 128 per-lane partial sums and adds them up once a
    query tile."""
    BH, Tq, _ = q.shape
    Tk = k.shape[1]
    dot = lambda a, b, dims: jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32)
    o, ms, ls = [], [], []
    for b in range(BH):
        for q0 in range(0, Tq, block):
            acc = jnp.zeros((block, v.shape[2]), jnp.float32)
            m = jnp.full((block, 1), fa.NEG_INF, jnp.float32)
            l = jnp.zeros((block, 1), jnp.float32)
            for k0 in range(0, Tk, block):
                if causal and q0 + block - 1 < k0:
                    continue
                s = dot(q[b, q0:q0 + block], k[b, k0:k0 + block],
                        ((1,), (1,))) * scale
                if bias3 is not None:
                    s = s + bias3[b, :, k0:k0 + block]
                if causal:
                    seen = (q0 + jnp.arange(block)[:, None]
                            >= k0 + jnp.arange(block)[None, :])
                    s = jnp.where(seen, s, fa.NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.where(s <= fa.NEG_INF / 2, 0.0, jnp.exp(s - m_new))
                corr = jnp.exp(m - m_new)
                l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                acc = acc * corr + dot(p.astype(v.dtype),
                                       v[b, k0:k0 + block], ((1,), (0,)))
                m = m_new
            o.append((acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype))
            ms.append(m)
            ls.append(l)
    fold = lambda xs: jnp.concatenate(xs).reshape(BH, Tq, -1)
    return fold(o), fold(ms), fold(ls)


def _places(a, b, dtype):
    """Largest distance between two arrays in representable values of
    ``dtype`` (finite numbers of one sign order as their bit patterns)."""
    bits = {jnp.float32: np.int32, jnp.bfloat16: np.int16}[dtype]
    a, b = (np.asarray(jnp.asarray(x, dtype)).view(bits).astype(np.int64)
            for x in (a, b))
    return int(np.abs(a - b).max())


def check_forward_forms(T, block, D, Dv, causal, masked):
    """The forward kernel on bf16 operands, as the cells feed it: in the
    row groups its shape gets, in ONE group (the form before PR 34) and in
    four where 128 rows divide the tile, forced as the two passes are: the
    same bits of o, m and l, for rows never mix. Against the tile-by-tile
    arithmetic before PR 34: m bit for bit, l within a few float32 places
    and o within one bfloat16 place of its value (where a key tile is whole
    128-lane tiles the denominator's terms are added lanes first, then
    across them once a query tile: 6 places at most on the chip at 4,096
    tokens, 4 here; where it is not, the order is the same and XLA:CPU's
    fusions, which run the interpreted kernel here, still move l by up to
    2). And within bfloat16's reach of the plain softmax."""
    BH, dtype, scale = 2, jnp.bfloat16, D ** -0.5
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(T + D + block), 3)
    q = jax.random.normal(kq, (BH, T, D), dtype)
    k = jax.random.normal(kk, (BH, T, D), dtype)
    v = jax.random.normal(kv, (BH, T, Dv), dtype)
    bias = None
    if masked:
        bias = jnp.where(jnp.arange(T)[None, :]
                         < jnp.array([T - 7, T])[:, None], 0.0, fa.NEG_INF)
    qp, kp, vp = (fa._pad_axis(x, 1, block)[0] for x in (q, k, v))
    bias3 = fa._pad_bias3(bias, BH, T, kp.shape[1])
    nk = kp.shape[1] // block

    def forward(groups=None):
        with pytest.MonkeyPatch.context() as patch:
            if groups is not None:
                patch.setattr(fa, "_fwd_groups", lambda block_q, nk: groups)
            # a jit of its own: a trace that sees the patched groups
            return jax.jit(lambda *a: fa._fa_pallas(
                *a, causal=causal, scale=scale, block_q=block, block_k=block,
                interpret=True))(qp, kp, vp, bias3)

    landed = forward()
    want_groups = 2 if block == 512 and nk == 1 else 1
    assert fa._fwd_groups(block, nk) == want_groups
    for groups in {1, want_groups, 4 if block % 512 == 0 else 1}:
        for name, a, b in zip("oml", forward(groups), landed):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
                (name, groups)
    before = _tiled_forward_as_before(qp, kp, vp, bias3, causal=causal,
                                      scale=scale, block=block)
    o, m, l = landed
    assert np.asarray(m).tobytes() == np.asarray(before[1]).tobytes()
    assert _places(l, before[2], jnp.float32) <= 8
    # one bfloat16 place (2**-8 to 2**-7 of the value) but for sums that
    # cancel to nearly nothing, where a float32 place of the terms shows
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(before[0], np.float32),
                               rtol=2.0 ** -7, atol=1e-5)
    ref_o, ref_m, ref_l = _reference_partial(
        q[:, :, None], k[:, :, None], v[:, :, None], bias, causal=causal,
        scale=scale)
    np.testing.assert_allclose(np.asarray(o[:, :T], np.float32),
                               np.asarray(ref_o[:, :, 0], np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(m[:, :T, 0], ref_m[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l[:, :T, 0], ref_l[:, 0], rtol=1e-5)


# (T, block): a 512-row tile is two groups against one key tile and one
# against several; tiles of 48 and 16 rows are one group with a denominator
# a row (a key tile is no whole number of 128-lane tiles). Then the depth,
# causal or not, a key-padding bias or not.
FORWARD_TILES = {"one_tile_512": (512, 512), "two_tiles_512": (1024, 512),
                 "one_tile_48": (48, 48), "four_tiles_16": (64, 16),
                 "padded_to_48": (40, 16)}
FORWARD_CASES = [
    (tile, D, causal, masked)
    for tile, depths, kinds in (
        ("one_tile_512", (64, 128), ((True, False), (False, True))),
        ("two_tiles_512", (64, 128), ((True, False), (False, True),
                                      (True, True))),
        ("one_tile_48", (64,), ((True, True), (False, False))),
        ("four_tiles_16", (64,), ((True, False), (False, True),
                                  (True, True), (False, False))),
        ("padded_to_48", (64,), ((True, False), (False, True),
                                 (True, True), (False, False))))
    for D in depths for causal, masked in kinds]


def forward_case_id(case):
    tile, D, causal, masked = case
    return "-".join([tile, f"{D}_deep", "causal" if causal else "full"]
                    + ["masked"] * masked)


@pytest.mark.parametrize("case", FORWARD_CASES, ids=forward_case_id)
def test_flash_forward_forms_agree(case):
    tile, D, causal, masked = case
    check_forward_forms(*FORWARD_TILES[tile], D, D, causal, masked)


def test_flash_bwd_form_follows_the_vmem_a_dq_row_needs(monkeypatch):
    """The choice among the three forms is made from shapes alone: the sum
    of ``_tiled_bwd_vmem_bytes`` against the module's VMEM constant."""
    # the latent-attention cell's shape: 16.5 MiB (blocks twice 7.75, the
    # float32 accumulators 4.75 of which dQ's row 4, four float32 tiles 4)
    need = fa._tiled_bwd_vmem_bytes(4096, 192, 128, 512, 512, 2, False)
    assert need == 17301504
    assert fa._VMEM_SCOPED_DEFAULT < need < fa._VMEM_BYTES
    longest = max(T for T in range(4096, 1 << 18, 4096)
                  if fa._tiled_bwd_vmem_bytes(T, 128, 128, 512, 512, 2,
                                              False) <= fa._VMEM_BYTES)
    assert longest == 57344       # 128-deep heads: the two passes beyond

    q = jnp.ones((1, 64, 1, 32), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16).sum()

    assert _bwd_kernels(loss, q, q, q) == BWD_KERNELS["fused_tiled"]
    need = fa._tiled_bwd_vmem_bytes(64, 32, 32, 16, 16, 2, False)
    for limit, form in ((need, "fused_tiled"), (need - 1, "two_pass")):
        monkeypatch.setattr(fa, "_VMEM_BYTES", limit)
        jax.clear_caches()
        assert _bwd_kernels(loss, q, q, q) == BWD_KERNELS[form]
    jax.clear_caches()


def test_flash_residual_path_still_differentiable():
    """return_residuals=True keeps the recompute vjp (m/l cotangents from
    merge_partials must flow)."""
    B, T, H, D = 1, 32, 2, 16
    q = _rand((B, T, H, D), 23)
    k1 = _rand((B, T, H, D), 24)
    v1 = _rand((B, T, H, D), 25)
    k2 = _rand((B, T, H, D), 26)
    v2 = _rand((B, T, H, D), 27)

    def loss(q, k1, v1, k2, v2):
        o1, (m1, l1) = flash_attention(q, k1, v1, causal=False,
                                       return_residuals=True,
                                       block_q=16, block_k=16)
        o2, (m2, l2) = flash_attention(q, k2, v2, causal=False,
                                       return_residuals=True,
                                       block_q=16, block_k=16)
        o, _, _ = merge_partials((o1, m1, l1), (o2, m2, l2))
        return (o.astype(jnp.float32) ** 2).sum()

    def ref_loss(q, k1, v1, k2, v2):
        k = jnp.concatenate([k1, k2], axis=1)
        v = jnp.concatenate([v1, v2], axis=1)
        o, _, _ = _reference_partial(q, k, v, causal=False, scale=D ** -0.5)
        return (o.astype(jnp.float32) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k1, v1, k2, v2)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2, 3, 4))(q, k1, v1, k2, v2)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bwd_form", ["two_pass", "fused_tiled", "fused"],
                         indirect=True)
def test_flash_partitioned_under_gspmd_mesh(monkeypatch, bwd_form):
    """flash_attention inside jit with dp x tp sharded operands: the
    custom_partitioning rule shards batch*head and replicates seq/depth, so
    the kernel runs per-shard and matches the unsharded result, whichever
    backward the shape selects."""
    monkeypatch.setenv("HOROVOD_FLASH_PARTITION", "1")
    blocks = {} if bwd_form == "fused" else {"block_q": 32, "block_k": 32}
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    B, T, H, D = 4, 64, 4, 32
    q = _rand((B, T, H, D), 30)
    k = _rand((B, T, H, D), 31)
    v = _rand((B, T, H, D), 32)
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "tp"))
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    def loss(q, k, v):
        return (flash_attention(q, k, v, causal=True, **blocks) ** 2).sum()

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    hlo = f.lower(qs, ks, vs).compile().as_text()
    assert [n for n in fa.KERNEL_BWD if n + "/" in hlo] == \
        sorted(BWD_KERNELS[bwd_form], key=fa.KERNEL_BWD.index)
    val_s, grads_s = f(qs, ks, vs)
    val_r, grads_r = f(q, k, v)  # unsharded oracle (same jit, fresh compile)
    np.testing.assert_allclose(float(val_s), float(val_r), rtol=1e-4)
    for a, b in zip(grads_s, grads_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
