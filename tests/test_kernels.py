"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def rand(shape, dtype=jnp.float32, scale=1.0):
    x = RNG.normal(size=shape).astype(np.float32) * scale
    return jnp.asarray(x, dtype=dtype)


# -- block quantization ----------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (64, 512), (3, 7),
                                   (1, 1), (2, 4, 384), (1000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_quant_roundtrip_bound(shape, dtype):
    x = rand(shape, dtype, scale=10.0)
    q, s, meta = ops.quantize_blocks(x)
    back = ops.dequantize_blocks(q, s, meta, dtype=jnp.float32)
    err = jnp.abs(back - x.astype(jnp.float32)).max()
    bound = jnp.abs(x.astype(jnp.float32)).max() / 127.0 + 1e-6
    assert err <= bound, (shape, dtype, float(err), float(bound))


@pytest.mark.parametrize("shape", [(8, 128), (32, 256), (64, 1024)])
def test_block_quant_matches_ref(shape):
    x = rand(shape)
    q, s, _ = ops.quantize_blocks(x)
    qr, sr = ref.quantize_blocks_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_block_quant_zero_tile():
    x = jnp.zeros((8, 128))
    q, s, meta = ops.quantize_blocks(x)
    back = ops.dequantize_blocks(q, s, meta)
    assert jnp.all(back == 0)


# -- decode attention ---------------------------------------------------------------

@pytest.mark.parametrize("B,H,kv,hd,C", [
    (1, 4, 4, 64, 256),       # MHA
    (2, 8, 2, 64, 512),       # GQA
    (2, 8, 1, 128, 1024),     # MQA
    (1, 16, 4, 80, 640),      # odd head_dim (zamba-like), pad path
])
@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, kv, hd, C, window, dtype):
    q = rand((B, 1, H, hd), dtype)
    k = rand((B, C, kv, hd), dtype)
    v = rand((B, C, kv, hd), dtype)
    kpos = jnp.broadcast_to(jnp.arange(C)[None], (B, C)).astype(jnp.int32)
    kpos = jnp.where(kpos > C - 50, -1, kpos)          # empty ring slots
    pos = jnp.full((B,), C - 50, jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    out = ops.decode_attention(q, k, v, kpos, pos, window, scale)
    expect = ref.decode_attention_ref(q, k, v, kpos, pos, window, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@pytest.mark.parametrize("H,kv,hd", [(8, 2, 64), (4, 4, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_reads_rows_by_slot(H, kv, hd, dtype):
    """Query rows pick their cache rows out of a larger buffer by slot
    (repeated slots included), as a serving replica's KV slab is read:
    the same result as the oracle on the gathered rows."""
    N, C = 5, 1024
    slots = jnp.asarray([3, 0, 4, 4], jnp.int32)
    q = rand((4, 1, H, hd), dtype)
    k = rand((N, C, kv, hd), dtype)
    v = rand((N, C, kv, hd), dtype)
    fill = jnp.asarray(RNG.integers(1, C, (N, 1)))
    kpos = jnp.where(jnp.arange(C)[None] < fill, jnp.arange(C)[None], -1
                     ).astype(jnp.int32)
    pos = jnp.asarray([700, 20, 1000, 1000], jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    out = ops.decode_attention(q, k, v, kpos, pos, None, scale, slots)
    expect = ref.decode_attention_ref(q, k[slots], v[slots], kpos[slots],
                                      pos, None, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@pytest.mark.parametrize("H,kv,hd,window", [(32, 8, 64, None), (8, 2, 64, 300),
                                             (4, 4, 32, None)])
def test_packed_decode_attention_reads_rows_by_slot(H, kv, hd, window):
    """Packed caches ``[N, C, kv*hd]`` (heads narrower than the lanes, as
    Granite-4.0-H's 8 KV heads of 64 are kept), read by slot with repeated
    slots: the oracle's result on the gathered rows, unpacked."""
    N, C = 5, 1024
    slots = jnp.asarray([3, 0, 4, 4], jnp.int32)
    q = rand((4, 1, H, hd), jnp.float32)
    k = rand((N, C, kv, hd), jnp.float32)
    v = rand((N, C, kv, hd), jnp.float32)
    fill = jnp.asarray(RNG.integers(1, C, (N, 1)))
    kpos = jnp.where(jnp.arange(C)[None] < fill, jnp.arange(C)[None], -1
                     ).astype(jnp.int32)
    pos = jnp.asarray([700, 20, 1000, 1000], jnp.int32)
    out = ops.decode_attention(q, k.reshape(N, C, -1), v.reshape(N, C, -1),
                               kpos, pos, window, 0.015625, slots)
    expect = ref.decode_attention_ref(q, k[slots], v[slots], kpos[slots],
                                      pos, window, 0.015625)
    np.testing.assert_allclose(out, expect, atol=1e-5)


def test_decode_attention_masks_everything_empty():
    """All-empty cache: softmax denominator guard must not NaN."""
    B, H, kv, hd, C = 1, 2, 2, 64, 128
    q = rand((B, 1, H, hd))
    k = jnp.zeros((B, C, kv, hd))
    v = jnp.zeros((B, C, kv, hd))
    kpos = jnp.full((B, C), -1, jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    out = ops.decode_attention(q, k, v, kpos, pos, None, 0.125)
    assert bool(jnp.isfinite(out).all())


# -- SSD scan ------------------------------------------------------------------------

@pytest.mark.parametrize("B,nc,Q,H,P,N", [
    (1, 2, 16, 2, 16, 8),
    (2, 4, 32, 3, 32, 16),
    (1, 8, 64, 2, 64, 64),     # mamba2-like tile
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, nc, Q, H, P, N, dtype):
    xc = rand((B, nc, Q, H, P), dtype)
    dtc = jnp.asarray(RNG.uniform(0.001, 0.1, (B, nc, Q, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 1.5, (H,)), jnp.float32)
    Bc = rand((B, nc, Q, N), dtype)
    Cc = rand((B, nc, Q, N), dtype)
    st = rand((B, H, P, N))
    y, fin = ops.ssd_scan(xc, dtc, A, Bc, Cc, st)
    yr, fr = ref.ssd_scan_ref(xc, dtc, A, Bc, Cc, st)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr.reshape(y.shape), np.float32),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fr),
                               atol=tol, rtol=1e-3)


def test_ssd_scan_state_chaining():
    """Scanning 4 chunks at once == two 2-chunk calls chained via state."""
    B, nc, Q, H, P, N = 1, 4, 16, 2, 16, 8
    xc = rand((B, nc, Q, H, P))
    dtc = jnp.asarray(RNG.uniform(0.01, 0.1, (B, nc, Q, H)), jnp.float32)
    A = -jnp.ones((H,), jnp.float32)
    Bc = rand((B, nc, Q, N))
    Cc = rand((B, nc, Q, N))
    st0 = jnp.zeros((B, H, P, N))
    y_all, f_all = ops.ssd_scan(xc, dtc, A, Bc, Cc, st0)
    y1, f1 = ops.ssd_scan(xc[:, :2], dtc[:, :2], A, Bc[:, :2], Cc[:, :2], st0)
    y2, f2 = ops.ssd_scan(xc[:, 2:], dtc[:, 2:], A, Bc[:, 2:], Cc[:, 2:], f1)
    np.testing.assert_allclose(np.asarray(y_all),
                               np.concatenate([y1, y2], axis=1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(f_all), np.asarray(f2), atol=1e-4)


# -- kernels wired into the model ---------------------------------------------------

def test_model_use_kernel_paths_match():
    import importlib
    from repro.models import transformer as T
    key = jax.random.PRNGKey(0)
    cfg = importlib.import_module("repro.configs.mamba2_2_7b").smoke_config()
    params = T.init_lm(cfg, key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    l0, _ = T.forward(params, cfg, tokens, use_kernel=False)
    l1, _ = T.forward(params, cfg, tokens, use_kernel=True)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), atol=1e-4)
