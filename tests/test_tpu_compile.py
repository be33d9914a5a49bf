"""The Pallas kernels compile for a TPU v5e at published widths.

Interpret mode (every other kernel test) accepts block shapes that Mosaic
refuses, so these tests compile each kernel for a v5e that is described,
not attached, and check that the kernel survives as a ``tpu_custom_call``.
Nothing runs; a compile takes a second or two.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and under pytest-xdist every worker
imports this file while only the one that runs its tests loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import block_quant, decode_attention, ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [8, 8192])
def test_wire_quant_compiles(one_chip, rows):
    """The q8 wire codec's form: ``[R, 128]`` f32, R a power-of-two
    count of (8, 128) tiles."""
    c = block_quant.WIRE_C
    assert "tpu_custom_call" in compile_text(
        block_quant.quantize_blocks, one_chip, ((rows, c), jnp.float32))
    assert "tpu_custom_call" in compile_text(
        block_quant.dequantize_blocks, one_chip, ((rows, c), jnp.int8),
        ((rows // 8, 1), jnp.float32))


def test_quantize_blocks_2d_compiles(one_chip):
    """The stage pipeline's ``quant_impl="pallas"`` form: a whole
    ``[4096, 4096]`` bf16 activation slab."""
    assert "tpu_custom_call" in compile_text(
        block_quant.quantize_blocks, one_chip, ((4096, 4096), jnp.bfloat16))
    assert "tpu_custom_call" in compile_text(
        lambda q, s: block_quant.dequantize_blocks(q, s, dtype=jnp.bfloat16),
        one_chip, ((4096, 4096), jnp.int8), ((512, 32), jnp.float32))


@pytest.mark.parametrize("heads,kv,hd", [
    (24, 2, 128),       # starcoder2-3b: g = 12
    (8, 4, 256),        # gemma3-4b: g = 2
])
def test_decode_attention_compiles(one_chip, heads, kv, hd):
    B, C = 8, 4096
    fn = lambda q, k, v, kpos, pos: decode_attention.decode_attention(
        q, k, v, kpos, pos, None, hd ** -0.5)
    assert "tpu_custom_call" in compile_text(
        fn, one_chip, ((B, 1, heads, hd), jnp.bfloat16),
        ((B, C, kv, hd), jnp.bfloat16), ((B, C, kv, hd), jnp.bfloat16),
        ((B, C), jnp.int32), ((B,), jnp.int32))


def test_ssd_scan_compiles(one_chip):
    """mamba2-2.7b: chunk 256, 80 heads of head_dim 64, state 128."""
    B, nc, Q, H, P, N = 1, 4, 256, 80, 64, 128
    assert "tpu_custom_call" in compile_text(
        ssd_scan.ssd_scan, one_chip, ((B, nc, Q, H, P), jnp.bfloat16),
        ((B, nc, Q, H), jnp.float32), ((H,), jnp.float32),
        ((B, nc, Q, N), jnp.bfloat16), ((B, nc, Q, N), jnp.bfloat16),
        ((B, H, P, N), jnp.float32))
