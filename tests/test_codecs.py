"""Wire codec tests: JSON/LZ4 byte-exact, ZFP fixed-rate error bound.

LZ4 round-trip is property-tested with hypothesis over arbitrary byte
strings (the invariant DEFER's weights socket depends on).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import codecs

RNG = np.random.default_rng(0)


# -- JSON -------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
def test_json_roundtrip_exact(dtype):
    arr = (RNG.normal(size=(9, 13)) * 100).astype(dtype)
    c = codecs.JsonCodec()
    np.testing.assert_array_equal(c.decode(c.encode(arr)), arr)


# -- LZ4 -------------------------------------------------------------------------

@settings(max_examples=100, deadline=None)
@given(st.binary(min_size=0, max_size=4096))
def test_lz4_roundtrip_arbitrary_bytes(data):
    lz = codecs.Lz4Codec()
    assert lz.decompress(lz.compress(data)) == data


@settings(max_examples=30, deadline=None)
@given(st.binary(min_size=1, max_size=64), st.integers(2, 200))
def test_lz4_compresses_repetition(chunk, reps):
    lz = codecs.Lz4Codec()
    data = chunk * reps
    out = lz.compress(data)
    assert lz.decompress(out) == data
    if len(data) > 256:
        assert len(out) < len(data)            # repetitive data must shrink


def test_lz4_overlapping_match():
    # RLE-style overlap (offset < match length) exercises byte-wise copy
    data = b"a" * 1000 + b"bc" + b"a" * 7
    lz = codecs.Lz4Codec()
    assert lz.decompress(lz.compress(data)) == data


@settings(max_examples=100, deadline=None)
@given(st.binary(min_size=0, max_size=4096))
def test_lz4_vectorized_byte_exact_property(data):
    """The vectorized compressor must emit byte-identical streams to the
    pure-Python greedy reference for arbitrary inputs."""
    vec, ref = codecs.Lz4Codec(), codecs.Lz4Codec(vectorized=False)
    assert vec.compress(data) == ref.compress(data)


def test_lz4_vectorized_byte_exact_payloads():
    """Byte-exactness on random + structured payloads shaped like the wire
    actually carries (raw bytes, zfp streams, tiled, text, zeros)."""
    payloads = [
        b"",
        b"abc",
        bytes(RNG.integers(0, 256, 65536).astype(np.uint8)),      # random
        b"the quick brown fox jumps over the lazy dog " * 500,    # text
        np.zeros(5000, np.uint8).tobytes(),                       # zeros
        bytes(range(256)) * 40,                                   # tiled
        codecs.ZfpCodec(rate=16).encode(                          # zfp wire
            RNG.normal(size=(64, 128)).astype(np.float32)),
        codecs.ZfpCodec(rate=8).encode(
            RNG.normal(size=(64, 128)).astype(np.float32)),
    ]
    vec, ref = codecs.Lz4Codec(), codecs.Lz4Codec(vectorized=False)
    for data in payloads:
        out = vec.compress(data)
        assert out == ref.compress(data)
        assert vec.decompress(out) == data
        assert ref.decompress(out) == data


# -- ZFP ------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [8, 12, 16, 24])
@pytest.mark.parametrize("transform", [True, False])
def test_zfp_error_bound(rate, transform):
    z = codecs.ZfpCodec(rate=rate, transform=transform)
    arr = RNG.normal(size=(33, 57)).astype(np.float32) * 50
    back = z.decode(z.encode(arr))
    bound = z.error_bound(float(np.abs(arr).max()))
    assert np.abs(back - arr).max() <= bound


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 500), st.floats(1e-3, 1e3))
def test_zfp_roundtrip_any_length_and_scale(n, scale):
    z = codecs.ZfpCodec(rate=16)
    arr = (RNG.normal(size=n) * scale).astype(np.float32)
    back = z.decode(z.encode(arr))
    assert back.shape == arr.shape
    assert np.abs(back - arr).max() <= z.error_bound(float(np.abs(arr).max()))


def test_zfp_rate_controls_payload():
    arr = RNG.normal(size=(256, 256)).astype(np.float32)
    sizes = [len(codecs.ZfpCodec(rate=r).encode(arr)) for r in (8, 16)]
    assert sizes[0] < sizes[1] < arr.nbytes


def test_zfp_preserves_dtype_and_shape():
    arr = RNG.normal(size=(4, 5, 6)).astype(np.float32)
    z = codecs.ZfpCodec(rate=16)
    back = z.decode(z.encode(arr))
    assert back.shape == arr.shape and back.dtype == arr.dtype


def test_zfp_vectorized_byte_exact():
    """The batched (4,4,B)-layout lift must reproduce the per-axis
    reference bit-for-bit, encode and decode."""
    arr = RNG.normal(size=(37, 53)).astype(np.float32) * 3
    for rate in (8, 14, 24):
        vec = codecs.ZfpCodec(rate=rate)
        ref = codecs.ZfpCodec(rate=rate, vectorized=False)
        blob = vec.encode(arr)
        assert blob == ref.encode(arr)
        np.testing.assert_array_equal(vec.decode(blob), ref.decode(blob))


def test_zfp_lift_near_invertible():
    """zfp's integer lift drops a few low bits by design (they sit below the
    coded precision); round-trip error must stay within the handful of LSBs
    that ``error_bound`` budgets for."""
    from repro.core.codecs import _fwd_lift, _inv_lift
    q = RNG.integers(-2**28, 2**28, size=(10, 4, 4)).astype(np.int64)
    out = _inv_lift(_inv_lift(_fwd_lift(_fwd_lift(q, 1), 2), 2), 1)
    assert np.abs(out - q).max() <= 16


# -- composition (what the emulator charges) ------------------------------------

@pytest.mark.parametrize("ser,comp", [("json", "none"), ("json", "lz4"),
                                      ("zfp", "none"), ("zfp", "lz4")])
def test_roundtrip_all_configurations(ser, comp):
    arr = np.maximum(RNG.normal(size=4096).astype(np.float32), 0)
    back, stats = codecs.roundtrip(arr, ser, comp, zfp_rate=16)
    assert stats.wire_bytes > 0 and stats.encode_s >= 0
    if ser == "json":
        np.testing.assert_array_equal(back, arr)
    else:
        bound = codecs.ZfpCodec(rate=16).error_bound(float(np.abs(arr).max()))
        assert np.abs(back - arr).max() <= bound
