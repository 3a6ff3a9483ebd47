"""Flash attention with a window (``flash_attention(..., window=W)``: key j
visible to query i iff ``i - W < j <= i``): the forward and the backward's
three forms in interpret mode against materialised attention, the band's
grid against the tiles that hold a visible pair, a window no shorter than
the sequence against the unwindowed call bit for bit, the names of
windowed calls, and the ``custom_partitioning`` wrappers (their counter:
tests/test_spans.py)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ``horovod_tpu.ops`` re-exports the function under the module's name.
fa = importlib.import_module("horovod_tpu.ops.flash_attention")
from test_ops import (_bwd_kernels, bwd_form, forced_two_pass,  # noqa: E402,F401
                      pallas_names)

WINDOW_BWD = {"fused": ["hvd_flash_bwd_window"],
              "fused_tiled": ["hvd_flash_bwd_tiled_window"],
              "two_pass": ["hvd_flash_bwd_dkv_window",
                           "hvd_flash_bwd_dq_window"]}


def _qkv(seed, B, T, H, D, Dv, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (B, T, H, d), dtype)
                 for k, d in zip(keys, (D, D, Dv, Dv)))


def _materialised(q, k, v, window):
    """Plain softmax attention under the window's mask, written here."""
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = jnp.where((j <= i) & (j > i - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def test_the_names_of_windowed_calls_stand_beside_the_unwindowed_ones():
    assert fa.KERNEL_FWD == "hvd_flash_fwd"
    assert fa.KERNEL_BWD == ("hvd_flash_bwd_dq", "hvd_flash_bwd_dkv",
                             "hvd_flash_bwd", "hvd_flash_bwd_tiled")
    assert fa.KERNEL_FWD_WINDOW == "hvd_flash_fwd_window"
    assert fa.KERNEL_BWD_WINDOW == (
        "hvd_flash_bwd_dq_window", "hvd_flash_bwd_dkv_window",
        "hvd_flash_bwd_window", "hvd_flash_bwd_tiled_window")
    assert not set(fa.KERNEL_BWD_WINDOW + (fa.KERNEL_FWD_WINDOW,)) \
        & set(fa.KERNEL_BWD + (fa.KERNEL_FWD,))


# T, block_q, block_k, window: shorter than, equal to and longer than a
# block; sequences that are no multiple of the block; unequal blocks
SHAPES = [(64, 16, 16, 8), (64, 16, 16, 16), (64, 16, 16, 24),
          (40, 16, 16, 20), (100, 32, 32, 33), (64, 16, 32, 24),
          (64, 32, 16, 5), (72, 16, 8, 1)]
IDS = ["shorter", "a_block", "longer", "padded_to_48", "padded_to_128",
       "wide_key_blocks", "wide_query_blocks", "itself_alone"]


@pytest.mark.parametrize("T,block_q,block_k,window", SHAPES, ids=IDS)
def test_windowed_forward_and_backward_against_materialised_attention(
        T, block_q, block_k, window, bwd_form):
    """Output, dQ, dK and dV with a value depth of its own, as the fused
    tiled kernel and as the two passes."""
    q, k, v, do = _qkv(T + window, 2, T, 2, 24, 16)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=block_q, block_k=block_k)

    names = pallas_names(jax.grad(lambda *a: flash(*a).sum(),
                                  argnums=(0, 1, 2)), q, k, v)
    assert sorted(n for n in names if "bwd" in n) == WINDOW_BWD[bwd_form]
    assert [n for n in names if "bwd" not in n] == ["hvd_flash_fwd_window"]
    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(lambda *a: _materialised(*a, window), q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(vjp(do), ref_vjp(do), "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("T,window", [(32, 7), (32, 31), (20, 3)],
                         ids=["short", "all_but_one", "padded_to_24"])
def test_windowed_one_tile_backward_against_materialised_attention(T, window):
    q, k, v, do = _qkv(T, 2, T, 2, 16, 24)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=32, block_k=32)

    assert _bwd_kernels(lambda *a: flash(*a).sum(), q, k, v) == \
        WINDOW_BWD["fused"]
    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(lambda *a: _materialised(*a, window), q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(vjp(do), ref_vjp(do), "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("T,block,window", [(64, 16, 24), (40, 16, 9)],
                         ids=["four_tiles", "padded_to_48"])
def test_windowed_tiled_backward_equals_two_pass_bit_for_bit(T, block,
                                                             window):
    """bf16 operands, as the cell feeds the kernels: the band's tiled kernel
    sums dQ over a query tile's key tiles in ascending order, as the dQ pass
    does, and dK, dV over the key tile's query tiles."""
    q, k, v, do = _qkv(T, 2, T, 2, 128, 128, jnp.bfloat16)

    def grads():
        return jax.vjp(lambda *a: fa.flash_attention(
            *a, causal=True, window=window, block_q=block, block_k=block),
            q, k, v)[1](do)

    tiled = grads()
    with forced_two_pass():
        two_pass = grads()
    for name, a, b in zip("qkv", tiled, two_pass):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), "d" + name


@pytest.mark.parametrize("window", [64, 65, 1000])
@pytest.mark.parametrize("residuals", [False, True])
def test_a_window_no_shorter_than_the_sequence_is_the_unwindowed_call(
        window, residuals):
    """The same kernels under the same names, and every bit of the output
    and of the three gradients."""
    q, k, v, do = _qkv(3, 2, 64, 2, 16, 16, jnp.bfloat16)

    def flash(window):
        def f(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True, window=window,
                                     block_q=16, block_k=16,
                                     return_residuals=residuals)
            return out[0] if residuals else out
        return f

    plain_names = pallas_names(jax.grad(lambda *a: flash(None)(*a).sum(),
                                        argnums=(0, 1, 2)), q, k, v)
    assert pallas_names(jax.grad(lambda *a: flash(window)(*a).sum(),
                                 argnums=(0, 1, 2)), q, k, v) == plain_names
    assert not any("window" in n for n in plain_names)
    out, vjp = jax.vjp(flash(window), q, k, v)
    want, want_vjp = jax.vjp(flash(None), q, k, v)
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
    for a, b in zip(vjp(do), want_vjp(do)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_windowed_call_with_residuals_takes_the_recompute_path():
    """``return_residuals=True``: (m, l) are the window's, and the backward
    is the ``jnp`` recompute under the same mask."""
    q, k, v, do = _qkv(9, 1, 48, 2, 16, 16)

    def flash(q, k, v):
        o, (m, l) = fa.flash_attention(q, k, v, causal=True, window=10,
                                       block_q=16, block_k=16,
                                       return_residuals=True)
        return o, m, l

    def reference(q, k, v):
        return fa._reference_partial(q, k, v, causal=True, scale=0.25,
                                     window=10)

    (o, m, l), vjp = jax.vjp(flash, q, k, v)
    (ro, rm, rl), ref_vjp = jax.vjp(reference, q, k, v)
    np.testing.assert_allclose(o, _materialised(q, k, v, 10), atol=2e-5)
    np.testing.assert_allclose(m, rm, atol=1e-6)
    np.testing.assert_allclose(l, rl, rtol=1e-5)
    cts = (do, jnp.ones_like(m), jnp.ones_like(l))
    for a, b in zip(vjp(cts), ref_vjp(cts)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def _tiles_with_a_visible_pair(nq, nk, bq, bk, window):
    """By enumeration of every query: its visible keys are one run, so the
    key tiles from its first key's to its last's."""
    seen = set()
    for i in range(nq * bq):
        first, last = max(0, i - window + 1), min(i, nk * bk - 1)
        seen.update((i // bq, t) for t in range(first // bk, last // bk + 1))
    return sorted(seen)


@pytest.mark.parametrize("T,bq,bk,window", SHAPES + [
    (8192, 512, 512, 512), (8192, 512, 512, 513), (8192, 256, 512, 512),
    (4096, 512, 256, 100)], ids=IDS + [
    "the_cell", "one_more_key", "narrow_query_tiles", "narrow_key_tiles"])
def test_the_bands_grid_visits_every_tile_with_a_visible_pair_and_no_other(
        T, bq, bk, window):
    nq, nk = -(-T // bq), -(-T // bk)
    want = _tiles_with_a_visible_pair(nq, nk, bq, bk, window)
    assert fa.band_tiles(nq, nk, bq, bk, window) == want

    def steps(n, band):
        """What the inner grid axis computes, step by step, as the kernels
        and their index maps read it."""
        visited = []
        for i in range(n):
            for j in range(fa._band_steps(n, band)):
                tile, inside = fa._band_step(jnp.int32(i), jnp.int32(j), band)
                if bool(inside):
                    visited.append((i, int(tile)))
                else:       # a step past the band holds the band's last tile
                    assert int(tile) == visited[-1][1]
        return visited

    if T <= 128:
        by_query = steps(nq, lambda i: fa._key_band(i, bq, bk, nk, window))
        assert by_query == want
        by_key = steps(nk, lambda i: fa._query_band(i, bq, bk, nq, window))
        assert sorted((q, k) for k, q in by_key) == want
    # the grid pays no step for a tile outside the band, and at most one
    # idle step a row where the bands' lengths differ
    inner = fa._band_steps(nq, lambda i: fa._key_band(i, bq, bk, nk, window))
    assert len(want) <= nq * inner <= len(want) + nq * (inner - 1)
    if (T, bq, bk, window) == (8192, 512, 512, 512):
        assert (len(want), nq * nk, inner) == (31, 256, 2)


@pytest.mark.parametrize("bad", [
    dict(causal=False, window=4), dict(window=0), dict(window=-3)])
def test_a_window_needs_causal_self_attention_and_a_positive_count(bad):
    q, k, v, _ = _qkv(1, 1, 16, 1, 8, 8)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, **{"causal": True, **bad})
    with pytest.raises(ValueError, match="Tq == Tk"):
        fa.flash_attention(q, k[:, :8], v[:, :8], window=4)


def test_windowed_call_partitioned_under_a_gspmd_mesh(monkeypatch, bwd_form):
    """The ``custom_partitioning`` wrappers carry the window: batch*head
    sharded, sequence and depths replicated."""
    monkeypatch.setenv("HOROVOD_FLASH_PARTITION", "1")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    q, k, v, _ = _qkv(7, 4, 64, 4, 24, 16)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    sharded = [jax.device_put(x, NamedSharding(
        mesh, P("dp", None, "tp", None))) for x in (q, k, v)]

    def loss(q, k, v):
        return (fa.flash_attention(q, k, v, window=20, block_q=16,
                                   block_k=16) ** 2).sum()

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    (value, grads), (want, want_grads) = f(*sharded), f(q, k, v)
    np.testing.assert_allclose(value, want, rtol=1e-4)
    ref = jax.grad(lambda *a: (_materialised(*a, 20) ** 2).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    for got, w, r in zip(grads, want_grads, ref):
        np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got, r, rtol=2e-4, atol=2e-4)
