"""Codec hot-path microbenchmark: vectorized vs pure-Python-baseline encode
throughput, so codec perf regressions are visible in BENCH output.

Measures MB/s for the LZ4 block compressor (NumPy bulk-skip match finder
vs the PR 1 byte-at-a-time reference) and the ZFP transform coder (batched
(4,4,B)-layout lift vs the per-axis copying reference) on three payload
classes the wire actually carries: incompressible random bytes, a real ZFP
activation stream (what ZFP/LZ4 compresses in the chain), and tiled
repetitive data.  Also measures the wire codec's small-payload bypass on
a one-token decode-step frame (ISSUE 9): raw magic-prefixed .npy vs the
full serializer/LZ4 path, where the setup cost dominates at a few hundred
bytes.  Exits nonzero if the vectorized path loses to the baseline beyond
tolerance.

    PYTHONPATH=src python benchmarks/codec_microbench.py --min-speedup 1.0
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache
from repro.core import codecs


def _mbs(fn, payload_bytes: int, reps: int) -> float:
    fn()                                    # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return payload_bytes * reps / 1e6 / (time.perf_counter() - t0)


def run(reps: int = 3) -> list[dict]:
    rng = np.random.default_rng(0)
    acts = rng.normal(size=(256, 512)).astype(np.float32)
    payloads = {
        "random": bytes(rng.integers(0, 256, 1 << 19).astype(np.uint8)),
        "zfp_stream": codecs.ZfpCodec(rate=16).encode(acts),
        "tiled": bytes(rng.integers(0, 256, 1024).astype(np.uint8)) * 256,
    }
    ref_lz4 = codecs.Lz4Codec(vectorized=False)
    vec_lz4 = codecs.Lz4Codec()
    rows = []
    for name, data in payloads.items():
        assert vec_lz4.compress(data) == ref_lz4.compress(data)
        ref = _mbs(lambda: ref_lz4.compress(data), len(data), 1)
        vec = _mbs(lambda: vec_lz4.compress(data), len(data), reps)
        rows.append({"codec": "lz4_compress", "payload": name,
                     "mb": len(data) / 1e6, "ref_mb_s": ref, "vec_mb_s": vec,
                     "speedup": vec / ref})
    blob = vec_lz4.compress(payloads["tiled"])
    ref = _mbs(lambda: ref_lz4.decompress(blob), len(payloads["tiled"]), 1)
    vec = _mbs(lambda: vec_lz4.decompress(blob), len(payloads["tiled"]), reps)
    rows.append({"codec": "lz4_decompress", "payload": "tiled",
                 "mb": len(payloads["tiled"]) / 1e6, "ref_mb_s": ref,
                 "vec_mb_s": vec, "speedup": vec / ref})

    # the decode-step fast path: a one-token activation frame is a few
    # hundred bytes, where ZFP/LZ4 setup cost dwarfs any transfer saving —
    # the size-threshold bypass ships it as magic-prefixed raw .npy.
    # ref = the full codec path on the same frame, vec = the bypass.
    from repro.runtime.wire import WireCodec
    step = rng.normal(size=(1, 1, 128)).astype(np.float32)
    for ser, comp in (("zfp", "lz4"), ("q8", "none")):
        full = WireCodec(ser, comp, zfp_rate=16)
        fast = WireCodec(ser, comp, zfp_rate=16, small_bypass=4096)
        np.testing.assert_array_equal(
            fast.decode_array(fast.encode_array(step)), step)
        ref = _mbs(lambda: full.encode_array(step), step.nbytes, reps * 100)
        vec = _mbs(lambda: fast.encode_array(step), step.nbytes, reps * 100)
        rows.append({"codec": f"small_bypass[{ser}_{comp}]",
                     "payload": "token_step_512B",
                     "mb": step.nbytes / 1e6, "ref_mb_s": ref,
                     "vec_mb_s": vec, "speedup": vec / ref})

    ref_zfp = codecs.ZfpCodec(rate=16, vectorized=False)
    vec_zfp = codecs.ZfpCodec(rate=16)
    zblob = vec_zfp.encode(acts)
    assert zblob == ref_zfp.encode(acts)
    for op, ref_fn, vec_fn in (
            ("zfp_encode", lambda: ref_zfp.encode(acts),
             lambda: vec_zfp.encode(acts)),
            ("zfp_decode", lambda: ref_zfp.decode(zblob),
             lambda: vec_zfp.decode(zblob))):
        ref = _mbs(ref_fn, acts.nbytes, reps)
        vec = _mbs(vec_fn, acts.nbytes, reps)
        rows.append({"codec": op, "payload": "activations",
                     "mb": acts.nbytes / 1e6, "ref_mb_s": ref,
                     "vec_mb_s": vec, "speedup": vec / ref})
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--min-speedup", type=float, default=0.0,
                    help="exit nonzero if the geomean vectorized/baseline "
                         "speedup falls below this")
    args = ap.parse_args()
    rows = run(args.reps)
    emit("codec_microbench", rows)
    geomean = float(np.exp(np.mean([np.log(r["speedup"]) for r in rows])))
    print(f"geomean vectorized/baseline speedup: {geomean:.2f}x")
    if args.min_speedup and geomean < args.min_speedup:
        raise SystemExit(f"codec speedup {geomean:.2f}x < "
                         f"required {args.min_speedup}x")


if __name__ == "__main__":
    enable_compile_cache()
    main()
