"""Flash attention with a value depth of its own (latent attention: q and k
192 deep, v and o 128): forward (in row groups and whole) and the four
backward kernels (fused one tile, fused tiled, dQ pass, dK/dV pass) in
interpret mode against ``_reference_partial``."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ``horovod_tpu.ops`` re-exports the function under the module's name.
fa = importlib.import_module("horovod_tpu.ops.flash_attention")
from test_ops import (BWD_KERNELS, FORWARD_TILES,  # noqa: E402,F401
                      _bwd_kernels, bwd_form, check_forward_forms,
                      check_tiled_equals_two_pass)


def _qkv(seed, B, T, H, D, Dv):
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(kq, (B, T, H, D), jnp.float32),
            jax.random.normal(kk, (B, T, H, D), jnp.float32),
            jax.random.normal(kv, (B, T, H, Dv), jnp.float32),
            jax.random.normal(kd, (B, T, H, Dv), jnp.float32))


def _reference(q, k, v, causal):
    scale = 1.0 / q.shape[-1] ** 0.5
    return fa._reference_partial(q, k, v, causal=causal, scale=scale)[0]


# (T, block): one tile -> the fused backward; several -> the fused tiled
# kernel, or the two passes where they are forced.
@pytest.mark.parametrize("T,block,bwd_form", [
    (32, 32, "fused"),
    (64, 16, "fused_tiled"),
    (64, 16, "two_pass"),
    (40, 16, "fused_tiled"),                                 # padded to 48
    (40, 16, "two_pass"),
], ids=["one_tile", "four_tiles", "four_tiles_two_pass", "padded_tiles",
        "padded_tiles_two_pass"], indirect=["bwd_form"])
@pytest.mark.parametrize("D,Dv", [(24, 16), (16, 24)])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_and_backward_at_another_value_depth(T, block, bwd_form, D,
                                                     Dv, causal):
    kernels = BWD_KERNELS[bwd_form]
    q, k, v, do = _qkv(T + D, 2, T, 2, D, Dv)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=block,
                                  block_k=block)

    assert _bwd_kernels(lambda *a: flash(*a).sum(), q, k, v) == kernels
    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(lambda *a: _reference(*a, causal), q, k, v)
    assert out.shape == (2, T, 2, Dv)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(vjp(do), ref_vjp(do), "qkv"):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("T,block", [(64, 16), (40, 16)],
                         ids=["four_tiles", "padded_to_48"])
@pytest.mark.parametrize("masked", [False, True], ids=["", "masked"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_tiled_backward_equals_two_pass_bit_for_bit_at_192_128(causal, masked,
                                                               T, block):
    """The latent-attention cell's depths through the fused tiled kernel:
    dQ and dK 192 deep, dV 128, the two passes' bits."""
    check_tiled_equals_two_pass(T, block, 192, 128, causal, masked)


@pytest.mark.parametrize("causal,masked", [(True, False), (False, True)],
                         ids=["causal", "masked"])
@pytest.mark.parametrize("tile", list(FORWARD_TILES))
def test_forward_forms_agree_at_192_128(tile, causal, masked):
    """The latent-attention cell's depths through the forward: the
    accumulator is rescaled by the lane-whole correction 128 wide while q
    and k are 192 deep; row groups, the form before PR 34 and the plain
    softmax as at equal depths."""
    check_forward_forms(*FORWARD_TILES[tile], 192, 128, causal, masked)


def test_partitioned_under_a_gspmd_mesh_at_another_value_depth(monkeypatch,
                                                               bwd_form):
    """The ``custom_partitioning`` wrappers give v, o, dO and dV their own
    depth factor: batch*head sharded, both depths replicated."""
    monkeypatch.setenv("HOROVOD_FLASH_PARTITION", "1")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    q, k, v, _ = _qkv(7, 4, 64, 4, 24, 16)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "tp"))
    sharded = [jax.device_put(x, NamedSharding(
        mesh, P("dp", None, "tp", None))) for x in (q, k, v)]

    def loss(q, k, v):
        return (fa.flash_attention(q, k, v, block_q=32, block_k=32) ** 2
                ).sum()

    f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    (value, grads), (want, want_grads) = f(*sharded), f(q, k, v)
    np.testing.assert_allclose(value, want, rtol=1e-4)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
