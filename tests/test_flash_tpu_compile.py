"""The flash forward and the backward's three forms, and the dropless expert
layer whose programs are chosen by shape, compiled ahead of time
for a TPU v5e at the widths the benchmark's cells run. No chip is needed:
Mosaic and XLA:TPU are installed here and compile for a chip that is
described, not attached.
Interpret mode (every other flash test) cannot see what Mosaic refuses: a
dynamic slice off the tiling, or more VMEM than the call may hold."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest

fa = importlib.import_module("horovod_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """An executable compiled for an absent chip is written to the
    persistent cache but cannot be read back: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# BH, T, D, Dv, block, causal, bias -> the kernels, and the VMEM asked for
@pytest.mark.parametrize("BH,T,D,Dv,block,causal,bias,kernels,vmem", [
    (128, 512, 64, 64, 512, False, True, ["hvd_flash_bwd"], None),
    (64, 4096, 192, 128, 512, True, False, ["hvd_flash_bwd_tiled"],
     17301504),
    (16, 4096, 128, 128, 512, True, False, ["hvd_flash_bwd_tiled"], None),
    (4, 32768, 128, 128, 512, True, False, ["hvd_flash_bwd_tiled"],
     41418752),
    (2, 65536, 128, 128, 512, True, False,
     ["hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"], None),
    (8, 64, 64, 64, 8, True, False, ["hvd_flash_bwd_tiled"], None),
], ids=["bert_512_one_tile", "joyai_4096_192_128", "llama_4096_128",
        "row_32k", "row_64k_two_pass", "blocks_of_8_rows"])
def test_flash_backward_compiles_for_v5e(one_chip, no_compile_cache, BH, T,
                                         D, Dv, block, causal, bias, kernels,
                                         vmem):
    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stat = arg((BH, T, 1), jnp.float32)
    args = [arg((BH, T, D)), arg((BH, T, D)), arg((BH, T, Dv)),
            arg((BH, T, Dv)), stat, stat, stat,
            arg((BH, 1, T), jnp.float32) if bias else None]

    def backward(*a):
        return fa._fa_bwd_pallas(*a, causal=causal, scale=D ** -0.5,
                                 block_q=block, block_k=block,
                                 interpret=False)

    hlo = jax.jit(backward).lower(*args).compile().as_text()
    calls = re.findall(r"%(hvd_flash_bwd\w*?)(?:\.\d+)? = ", hlo)
    assert sorted(calls) == sorted(kernels)
    need = fa._tiled_bwd_vmem_bytes(T, D, Dv, block, block, 2, bias)
    if vmem is None:     # within Mosaic's default, or not the tiled kernel
        assert kernels != ["hvd_flash_bwd_tiled"] \
            or need <= fa._VMEM_SCOPED_DEFAULT
    else:
        assert need == vmem and f'"{vmem}"' in hlo


# BH, T, D, Dv, block, causal, bias -> row groups of a visit
@pytest.mark.parametrize("BH,T,D,Dv,block,causal,bias,groups", [
    (128, 512, 64, 64, 512, False, True, 2),
    (64, 4096, 192, 128, 512, True, False, 1),
    (16, 4096, 128, 128, 512, True, False, 1),
    (8, 64, 64, 64, 8, True, False, 1),
], ids=["bert_512_one_tile", "joyai_4096_192_128", "ouro_4096_128",
        "blocks_of_8_rows"])
def test_flash_forward_compiles_for_v5e(one_chip, no_compile_cache, BH, T, D,
                                        Dv, block, causal, bias, groups):
    """The three cells' forward calls and the tests' smallest tile: one
    custom call named ``hvd_flash_fwd``, in the row groups the shape gets,
    within Mosaic's default scoped VMEM (no limit is asked for)."""
    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [arg((BH, T, D)), arg((BH, T, D)), arg((BH, T, Dv)),
            arg((BH, 1, T), jnp.float32) if bias else None]

    def forward(*a):
        return fa._fa_pallas(*a, causal=causal, scale=D ** -0.5,
                             block_q=block, block_k=block, interpret=False)

    assert fa._fwd_groups(block, T // block) == groups
    hlo = jax.jit(forward).lower(*args).compile().as_text()
    assert re.findall(r"%(hvd_flash\w*?)(?:\.\d+)? = ", hlo) == \
        [fa.KERNEL_FWD] == ["hvd_flash_fwd"]
    # a call that asks for a VMEM limit carries it as a scoped memory config
    assert '"scoped_memory_configs":[]' in hlo and \
        '"scoped_memory_configs":[{' not in hlo


# BH, T, window -> the kernels, and the VMEM the tiled backward's shapes sum to
@pytest.mark.parametrize("BH,T,window,kernels,vmem", [
    (36, 8192, 512, ["hvd_flash_fwd_window", "hvd_flash_bwd_tiled_window"],
     16252928),
    (24, 8192, None, ["hvd_flash_fwd", "hvd_flash_bwd_tiled"], 16252928),
    (2, 65536, 512, ["hvd_flash_fwd_window", "hvd_flash_bwd_dq_window",
                     "hvd_flash_bwd_dkv_window"], None),
], ids=["laguna_sliding_8192", "laguna_full_8192", "row_64k_two_pass"])
def test_windowed_flash_compiles_for_v5e(one_chip, no_compile_cache, BH, T,
                                         window, kernels, vmem):
    """The layer-pattern cell's two calls at 128-deep heads in tiles of 512:
    Mosaic takes the band's index maps (a traced first tile added to the
    step), the dQ scratch zeroed and written by the band's own first and
    last key tile, and the windowed names reach the compiled program."""
    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stat = arg((BH, T, 1), jnp.float32)
    qkv = [arg((BH, T, 128))] * 3
    kw = dict(causal=True, scale=128 ** -0.5, block_q=512, block_k=512,
              interpret=False, window=window)
    forward = jax.jit(lambda *a: fa._fa_pallas(*a, None, **kw))
    backward = jax.jit(lambda *a: fa._fa_bwd_pallas(*a, None, **kw))
    hlo = forward.lower(*qkv).compile().as_text() \
        + backward.lower(*qkv, qkv[0], stat, stat, stat).compile().as_text()
    assert sorted(re.findall(r"%(hvd_flash\w*?)(?:\.\d+)? = ", hlo)) == \
        sorted(kernels)
    if vmem is not None:    # within Mosaic's default: no limit is asked for
        assert fa._tiled_bwd_vmem_bytes(T, 128, 128, 512, 512, 2, False) \
            == vmem <= fa._VMEM_SCOPED_DEFAULT
        assert '"scoped_memory_configs":[{' not in hlo
    if window is not None:
        assert len(fa.band_tiles(T // 512, T // 512, 512, 512, window)) \
            == 2 * (T // 512) - 1


# T, D, M, k, held -> the window's rows, and the form the token side takes
@pytest.mark.parametrize("T,D,M,k,held,window,by_rows", [
    (8192, 3072, 1024, 10, 8, 5120, True),
    (8192, 2048, 768, 8, 16, 8192, False),
], ids=["laguna_8192_k10_8_of_256", "joyai_8192_k8_16_of_256"])
def test_dropless_share_compiles_for_v5e(one_chip, no_compile_cache, T, D, M,
                                         k, held, window, by_rows):
    """Both expert cells' layer, forward and backward: where the token side
    sums row by row the optimized program holds no array of the pairs,
    ``[T, k, D]`` or flattened; where it sums pair by pair it holds them."""
    from horovod_tpu.parallel import moe

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, logits, bias, w1, w3, w2):
        def loss(x, logits, w1, w3, w2):
            return (moe.dropless_share(x, logits, bias, w1, w3, w2, first=8,
                                       top_k=k, scaling=2.5)[0] ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, logits, w1, w3, w2)

    # the CPU's row tile of 8 and the chip's 512 give the same window here
    assert moe.plan_rows(T * k, held, 256) == window
    assert moe._sums_by_rows(window, T * k) == by_rows
    hlo = jax.jit(both).lower(
        arg((T, D)), arg((T, 256), jnp.float32), arg((256,), jnp.float32),
        arg((held, D, M)), arg((held, D, M)), arg((held, M, D))
    ).compile().as_text()
    pairs = [f"[{T},{k},{D}]", f"[{T * k},{D}]"]
    assert any(shape in hlo for shape in pairs) == (not by_rows)
