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


def _slab_programs(one_chip, monkeypatch):
    """A compute node's slot write and 8-row decode step, compiled at
    starcoder2-3b's KV widths (2 KV heads of 128, 4096 positions, 65 slab
    rows) with the ``decode_attention`` kernel, and the slab's bytes."""
    import numpy as np

    from repro.kernels import ops
    from repro.models.lm_graph import decode_lm_graph
    from repro.runtime.node import ComputeNode
    from repro.runtime.wire import WireCodec

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    g = decode_lm_graph(vocab=256, d_model=3072, n_layers=1, num_heads=24,
                        kv_heads=2, head_dim=128, d_ff=256, cache_len=4096,
                        use_kernel=True)
    node = ComputeNode(0, WireCodec("raw", "none"), session_capacity=64)
    node._graph = g
    node._set_range(0, len(g.nodes))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    node._params = {n.name: jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype), n.param_spec) for n in g.nodes}
    node._make_apply()
    _, caches = jax.eval_shape(node._prefill_apply,
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))
    caches = jax.tree_util.tree_map(lambda c: sds(c.shape, c.dtype), caches)
    slab = jax.tree_util.tree_map(
        lambda c: sds((65,) + c.shape[1:], c.dtype), caches)
    slab_bytes = sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(slab))
    step, rows = node._decode_apply, sds((8,), jnp.int32)
    write = node._write_slot.lower(slab, caches, sds((), jnp.int32))
    step = step.func.lower(*step.args, slab, rows, sds((8, 1), jnp.int32),
                           rows)
    return write.compile(), step.compile(), slab_bytes


def test_decode_step_keeps_the_kv_slab_in_place(one_chip, monkeypatch):
    """The donated slab aliases its output in the slot write and the step,
    and every op of the slab's shape keeps the slab's one layout, so no
    program converts (copies) the whole slab."""
    import re

    write, step, slab_bytes = _slab_programs(one_chip, monkeypatch)
    for compiled, kernel in ((write, False), (step, True)):
        text = compiled.as_text()
        assert ("tpu_custom_call" in text) == kernel
        # aliased bytes count the tiles' padding too
        assert compiled.memory_analysis().alias_size_in_bytes >= slab_bytes
        full = re.findall(r"= \(?f32\[65,4096,2,128\]\{([^}]*)\} ([a-z-]+)\(",
                          text)
        assert full and len({layout for layout, _ in full}) == 1, full
        assert "copy" not in {op for _, op in full}


def test_decode_kernel_reads_the_wave_rows_in_the_slab(one_chip,
                                                       monkeypatch):
    """The step hands ``decode_attention`` the slab itself, as a free
    reshape, and the kernel picks the wave's rows by slot: no op of the
    step gathers the 8 rows' caches, in any layout, so all the KV the
    kernel reads is read inside the kernel."""
    import re

    _, step, _ = _slab_programs(one_chip, monkeypatch)
    text = step.as_text()
    call = re.search(r"%decode_attention[.\d]* = .*", text)
    assert call and "f32[65,8192,128]" in call.group(0)
    ops = {op for op in re.findall(
        r"= \(?f32\[65,8192,128\]\{[^}]*\} ([a-z-]+)\(", text)}
    assert ops == {"bitcast"}, ops
    rows = re.findall(r"f32\[8,(?:4096,2,128|2,4096,128|8192,128)\]", text)
    assert not rows, rows


def test_ssm_step_compiles_and_aliases_the_slab(one_chip):
    """Granite-4.0-H-Micro's Mamba2 (64 heads of 64, state 128): a 16-row
    wave against a 17-row slab; the slab is the new state's buffer."""
    from repro.kernels import ssm_step

    R, B, H, P, N = 17, 16, 64, 64, 128
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((R, H, P, N), jnp.float32), ((B,), jnp.int32), ((B, H, P),
                                                         jnp.float32),
        ((B, H), jnp.float32), ((H,), jnp.float32), ((B, N), jnp.float32),
        ((B, N), jnp.float32))]
    compiled = jax.jit(ssm_step.ssm_step, donate_argnums=0).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= R * H * P * N * 4


def test_packed_decode_attention_reads_the_cache_in_its_layout(one_chip):
    """8 KV heads of 64 in f32, packed ``[N, C, 512]``: the kernel reads
    the 16384-position cache as it lies, with no copy of it."""
    import re

    R, C, B = 17, 16384, 16
    fn = lambda q, k, v, kpos, pos, slots: \
        decode_attention.decode_attention_packed(q, k, v, kpos, pos, None,
                                                 0.015625, slots)
    text = compile_text(fn, one_chip, ((B, 1, 32, 64), jnp.float32),
                        ((R, C, 512), jnp.float32), ((R, C, 512), jnp.float32),
                        ((R, C), jnp.int32), ((B,), jnp.int32),
                        ((B,), jnp.int32))
    assert "tpu_custom_call" in text
    ops = set(re.findall(r"= \(?f32\[17,16384,512\]\{[^}]*\} ([a-z-]+)\(",
                         text))
    assert ops <= {"parameter"}, ops


def _hybrid_step(one_chip, monkeypatch, rows=17, wave=16):
    """Stage 1 of granite-4.0-h-micro's 2-stage chain (its attention layer,
    four Mamba2 layers and the head) at published widths and a 16384-slot
    cache: the decode step for a ``wave``-row wave against a ``rows``-row
    slab, compiled, and the slab's shapes."""
    import numpy as np

    from bench import spec
    from bench.models import hybrid_lm
    from repro.kernels import ops
    from repro.runtime.node import ComputeNode
    from repro.runtime.wire import WireCodec

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = spec.load_config(spec.load_benchmark(), "granite-4.0-h-micro")
    g = hybrid_lm.build_graph(cfg)
    node = ComputeNode(1, WireCodec("raw", "none"), max_batch=wave)
    node._graph = g
    node._set_range(11, len(g.nodes))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    node._params = {n.name: jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype), n.param_spec) for n in node._nodes}
    node._make_apply()
    _, caches = jax.eval_shape(node._prefill_apply,
                               jax.ShapeDtypeStruct((1, 8, 2048),
                                                    jnp.float32))
    slab = jax.tree_util.tree_map(
        lambda c: sds((rows,) + c.shape[1:], c.dtype), caches)
    step, idx = node._decode_apply, sds((wave,), jnp.int32)
    compiled = step.func.lower(*step.args, slab, idx,
                               sds((wave, 1, 2048), jnp.float32),
                               idx).compile()
    slab_bytes = sum(np.prod(a.shape) * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(slab))
    return compiled, slab, slab_bytes


def test_hybrid_decode_step_keeps_its_slab_in_place(one_chip, monkeypatch):
    """The hybrid's step aliases its whole slab, KV cache and recurrent
    state, from input to output; the two large leaves (KV ``[17, 16384,
    512]``, state ``[17, 64, 64, 128]``) appear only as parameters, free
    reshapes for the kernels and in-place writes of the wave's rows, and
    no leaf is ever converted (copied) to another layout."""
    import re

    compiled, slab, slab_bytes = _hybrid_step(one_chip, monkeypatch)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5      # 1 attention, 4 ssm_step
    assert compiled.memory_analysis().alias_size_in_bytes >= slab_bytes
    shapes = {a.shape for a in jax.tree_util.tree_leaves(slab)}
    assert (17, 16384, 512) in shapes and (17, 64, 64, 128) in shapes
    for shape in shapes:
        dims = ",".join(map(str, shape))
        found = re.findall(r"= \(?[fs]32\[" + dims + r"\]\{([^}]*)\} "
                           r"([a-z-]+)\(", text)
        assert found and "copy" not in {op for _, op in found}, (shape, found)
        hbm = {layout for layout, _ in found if "S(" not in layout}
        assert len(hbm) == 1, (shape, hbm)
        if shape in ((17, 16384, 512), (17, 64, 64, 128)):
            assert {op for _, op in found} <= {
                "parameter", "bitcast", "dynamic-update-slice"}, found
