"""Bring-up check: the DEFER serving chain on a TPU, through its entry points.

    python chip_smoke.py               # one chip: the default phases
    python chip_smoke.py --four-chips  # the SPMD stage pipeline on 4 chips

Default phases, one process, one chip, weights from ``--seed``:

1. ``resnet50-raw`` — the paper's model at full width (224x224x3, 1000
   classes) served by ``InferenceEngine`` over a 4-stage
   ``TopologySpec.chain`` with the lossless raw wire codec: 8 requests
   from 2 clients, each output held to ``jax.jit(graph.apply)`` on the
   same chip.
2. ``resnet50-q8`` — the same chain with the ``q8`` wire codec, which puts
   the ``block_quant`` Pallas kernel on the serving path: every output
   finite, one stage-boundary activation round-trips within
   ``WireCodec.error_bound``, and the codec's kernel is compiled for the
   TPU (``tpu_custom_call``), not interpreted.
3. ``decode`` — a 4-layer decoder at starcoder2-3b's widths with the
   ``decode_attention`` kernel: 2 concurrent ``engine.generate()``
   sessions over a 2-stage chain must produce ``pipeline_decode_reference``'s
   tokens, and the first step's logits with the kernel must match the
   plain-jnp attention's.

``--four-chips`` runs only the stage pipeline (``launch/serve.py``
``build_pipeline_lm``): phi3-mini-3.8b at full width in bf16, 4 stages on
4 chips, against a single-chip ``T.forward`` reference.

Without a TPU the script exits non-zero before any phase and prints no
result.  Any failed phase exits non-zero.  The last line of stdout is the
JSON result: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# Error bounds, fixed before any run.  Both sides of each comparison run on
# the same chip, but XLA's default TPU precision rounds f32 matmul and
# convolution operands to bf16 (unit roundoff 2**-9), and the two sides are
# different programs (per-stage jits at other batch sizes, Pallas kernels
# that keep f32), so they agree to bf16 rounding, not bit for bit.  Errors
# are normalised by the reference's largest magnitude: random-init logits
# have entries near zero, where an elementwise relative error is
# meaningless.
RESNET_TOL = 2e-2       # max|chain - jit(apply)| / max|jit(apply)|, raw codec
KERNEL_TOL = 2e-2       # first-step logits, decode_attention vs jnp attention
PIPELINE_TOL = 5e-2     # bf16 weights and activations through 32 layers


def log(msg: str) -> None:
    print(msg, flush=True)


def normalised_error(out, ref) -> tuple[float, float]:
    """(max-abs error, max-abs error / max-abs reference)."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.abs(out - ref).max())
    return err, err / max(float(np.abs(ref).max()), 1e-30)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- phases 1 and 2: ResNet50 through the serving chain ---------------------

def serve_chain(graph, params, codec, inputs):
    """Serve ``inputs`` (one list per client) through a 4-stage
    InferenceEngine chain; return the outputs per client, in submission
    order, and the chain's layer bounds."""
    from repro.runtime import InferenceEngine, TopologySpec
    from repro.runtime.dispatcher import DispatcherCodecs

    eng = InferenceEngine(graph, TopologySpec.chain(graph, 4),
                          DispatcherCodecs(data=codec), max_batch=4)
    try:
        t0 = time.perf_counter()
        eng.configure(params)
        eng.precompile()
        eng.start()
        log(f"  configure+precompile+start {time.perf_counter() - t0:.1f} s "
            f"(host clock, compiles included)")
        futures = [[eng.submit(x, client_id=c) for x in xs]
                   for c, xs in enumerate(inputs)]
        # .result() re-raises a NodeError: a failed stage fails the phase
        return [[np.asarray(f.result(timeout=300)) for f in fs]
                for fs in futures], eng.topology.bounds
    finally:
        eng.shutdown()


def resnet_inputs(graph, seed: int):
    """8 seeded requests: 4 from each of 2 clients."""
    rng = np.random.default_rng(seed)
    shape = graph.input_spec.shape
    return [[rng.normal(size=shape).astype(np.float32) for _ in range(4)]
            for _ in range(2)]


def phase_resnet_raw(graph, params, seed: int) -> None:
    import jax
    from repro.runtime.wire import WireCodec

    inputs = resnet_inputs(graph, seed)
    outs, _ = serve_chain(graph, params, WireCodec("raw", "none"), inputs)
    ref = jax.jit(graph.apply)
    worst = (0.0, 0.0)
    for xs, ys in zip(inputs, outs):
        for x, y in zip(xs, ys):
            worst = max(worst, normalised_error(y, ref(params, x)),
                        key=lambda e: e[1])
    log(f"  8 requests / 2 clients: max-abs err {worst[0]:.6g}, "
        f"max-abs err / max|ref| {worst[1]:.6g} (bound {RESNET_TOL})")
    check(worst[1] <= RESNET_TOL, "raw-codec chain disagrees with jit(apply)")


def phase_resnet_q8(graph, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import block_quant, interpret_mode
    from repro.runtime.wire import WireCodec

    check(not interpret_mode(), "Pallas kernels would be interpreted")
    probe = jnp.zeros((64, block_quant.WIRE_C), jnp.float32)
    hlo = block_quant.quantize_blocks.lower(
        probe, interpret=interpret_mode()).compile().as_text()
    check("tpu_custom_call" in hlo, "q8 encode did not lower to a kernel")
    log("  q8 encode compiles to tpu_custom_call (Mosaic, not interpreted)")

    codec = WireCodec("q8", "none")
    inputs = resnet_inputs(graph, seed)
    outs, bounds = serve_chain(graph, params, codec, inputs)
    ref = jax.jit(graph.apply)
    worst = (0.0, 0.0)
    for xs, ys in zip(inputs, outs):
        for x, y in zip(xs, ys):
            check(bool(np.isfinite(y).all()), "non-finite q8 output")
            worst = max(worst, normalised_error(y, ref(params, x)),
                        key=lambda e: e[1])
    log(f"  8 requests / 2 clients, all finite; end-to-end vs jit(apply) "
        f"(lossy at {len(bounds) - 2} hops, not bounded): max-abs err "
        f"{worst[0]:.6g}, / max|ref| {worst[1]:.6g}")

    act = np.asarray(jax.jit(lambda p, x: graph.apply(
        p, x, nodes=graph.slice_nodes(bounds[0], bounds[1])))(
            params, inputs[0][0]))
    back = codec.decode_array(codec.encode_array(act))
    err = float(np.abs(back - act).max())
    bound = codec.error_bound(float(np.abs(act).max()))
    log(f"  stage-0 boundary activation {act.shape}: round-trip err "
        f"{err:.6g} (error_bound {bound:.6g})")
    check(err <= bound, "q8 round trip exceeds WireCodec.error_bound")


# -- phase 3: decode sessions with the decode_attention kernel --------------

# starcoder2-3b's widths (configs/starcoder2_3b.py), depth cut to 4 layers
DECODE_WIDTHS = dict(vocab=49152, d_model=3072, num_heads=24, kv_heads=2,
                     head_dim=128, d_ff=12288)
DECODE_LAYERS, DECODE_CACHE, NEW_TOKENS = 4, 2048, 16


def first_step_logits(graph, params, prompt):
    """Prefill ``prompt`` then run one decode step; return its logits."""
    import jax.numpy as jnp

    acts = jnp.asarray(np.asarray(prompt, np.int32).reshape(1, -1))
    caches = {}
    for node in graph.nodes:
        p = params[node.name]
        if node.decode is not None:
            acts, caches[node.name] = node.decode.prefill_fn(p, acts)
        else:
            acts = node.fn(p, acts)
    acts = jnp.asarray([[int(np.argmax(np.asarray(acts[0, -1])))]], jnp.int32)
    pos = jnp.asarray([len(prompt)], jnp.int32)
    for node in graph.nodes:
        p = params[node.name]
        if node.decode is not None:
            acts, _ = node.decode.step_fn(p, caches[node.name], acts, pos)
        else:
            acts = node.fn(p, acts)
    return np.asarray(acts[0, -1], np.float32)


def phase_decode(seed: int) -> None:
    import jax
    from repro.models.lm_graph import (decode_lm_graph,
                                       pipeline_decode_reference)
    from repro.runtime import InferenceEngine, TopologySpec
    from repro.runtime.dispatcher import DispatcherCodecs
    from repro.runtime.wire import WireCodec

    graph = decode_lm_graph(n_layers=DECODE_LAYERS, cache_len=DECODE_CACHE,
                            use_kernel=True, **DECODE_WIDTHS)
    params = graph.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, DECODE_WIDTHS["vocab"], n).tolist()
               for n in (5, 9)]

    plain = decode_lm_graph(n_layers=DECODE_LAYERS, cache_len=DECODE_CACHE,
                            use_kernel=False, **DECODE_WIDTHS)
    with_kernel = first_step_logits(graph, params, prompts[0])
    without = first_step_logits(plain, params, prompts[0])
    err, rel = normalised_error(with_kernel, without)
    log(f"  first-step logits, kernel vs jnp attention: max-abs err "
        f"{err:.6g}, / max|ref| {rel:.6g} (bound {KERNEL_TOL})")
    check(rel <= KERNEL_TOL, "decode_attention kernel disagrees with jnp")

    eng = InferenceEngine(graph, TopologySpec.chain(graph, 2),
                          DispatcherCodecs(data=WireCodec("raw", "none")),
                          max_batch=2)
    outs: list[list[int]] = [[] for _ in prompts]
    errors: list[BaseException] = []

    def session(i: int) -> None:
        try:
            outs[i].extend(eng.generate(prompts[i], NEW_TOKENS))
        except BaseException as e:  # re-raised on the main thread below
            errors.append(e)

    try:
        t0 = time.perf_counter()
        eng.configure(params)
        eng.precompile()
        eng.start()
        log(f"  configure+precompile+start {time.perf_counter() - t0:.1f} s "
            f"(host clock, compiles included)")
        threads = [threading.Thread(target=session, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        check(not any(t.is_alive() for t in threads), "a session hung")
    finally:
        eng.shutdown()
    if errors:
        raise errors[0]
    for p, out in zip(prompts, outs):
        ref = pipeline_decode_reference(graph, params, p, NEW_TOKENS)
        log(f"  session prompt len {len(p)}: {len(out)} tokens, "
            f"{sum(a == b for a, b in zip(out, ref))}/{len(ref)} equal to "
            f"the single-device reference")
        check(out == ref, f"decode tokens {out} != reference {ref}")


# -- --four-chips: the SPMD stage pipeline ------------------------------------

# phi3-mini-3.8b at full width and depth; 8 requests of 64 tokens in 4
# microbatches through 4 stages
PIPE_STAGES, PIPE_MICROBATCHES, PIPE_BATCH, PIPE_SEQ = 4, 4, 8, 64


def phase_four_chips(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_auto_mesh
    from repro.launch.serve import build_pipeline_lm, init_stage_params
    from repro.models import transformer as T

    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (PIPE_BATCH, PIPE_SEQ), 0, cfg.vocab)

    # single-chip reference first; only its logits outlive it, on the host
    params = jax.jit(lambda k: T.init_lm(cfg, k, jnp.bfloat16))(key)
    n_params = T.param_count(params)
    ref = np.asarray(jax.jit(lambda p, t: T.forward(p, cfg, t)[0])(
        params, tokens), np.float32)
    del params
    log(f"  reference: {cfg.name}, {n_params} params in bf16 "
        f"({2 * n_params} bytes), T.forward on {jax.devices()[0]}")

    mesh = make_auto_mesh((PIPE_STAGES,), ("stage",))
    rest, stage_params = init_stage_params(cfg, key, mesh, PIPE_STAGES,
                                           dtype=jnp.bfloat16)
    stacked, _ = stage_params
    for leaf in jax.tree_util.tree_leaves(stacked):
        owners = {s.device: s.index[0] for s in leaf.addressable_shards}
        check(len(owners) == PIPE_STAGES
              and sorted(i.start for i in owners.values())
              == list(range(PIPE_STAGES)),
              "stage weights not one stage per chip")
    log(f"  stage weights: one stage's units per device "
        f"({len(jax.tree_util.tree_leaves(stacked))} stacked leaves checked)")

    lm = build_pipeline_lm(cfg, rest, mesh, PIPE_STAGES, PIPE_MICROBATCHES,
                           stage_params=stage_params)
    with mesh:
        out = np.asarray(jax.jit(lambda t: lm(t))(tokens), np.float32)
    err, rel = normalised_error(out, ref)
    agree = float((out.argmax(-1) == ref.argmax(-1)).mean())
    log(f"  pipeline vs single-chip forward: max-abs err {err:.6g}, "
        f"/ max|ref| {rel:.6g} (bound {PIPELINE_TOL}); argmax agreement "
        f"{agree:.4f} (not asserted)")
    for d in jax.devices()[:PIPE_STAGES]:
        stats = d.memory_stats() or {}
        log(f"  {d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
            f"bytes_in_use {stats.get('bytes_in_use')}")
        if d != jax.devices()[0]:       # device 0 also held the reference
            check(stats.get("peak_bytes_in_use", 0) < 2 * n_params,
                  f"{d} held a whole model's worth of weights")
    check(bool(np.isfinite(out).all()), "non-finite pipeline logits")
    check(rel <= PIPELINE_TOL, "stage pipeline disagrees with T.forward")


# -- entry point --------------------------------------------------------------

def run_phase(name: str, fn, *args) -> None:
    log(f"[{name}]")
    t0 = time.perf_counter()
    fn(*args)
    log(f"[{name}] passed in {time.perf_counter() - t0:.1f} s (host clock)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD stage pipeline on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: no TPU found: JAX {jax.__version__} sees "
            f"{len(devices)} {dev.platform} device(s)")
        return 1
    if args.four_chips and len(devices) < PIPE_STAGES:
        log(f"chip_smoke: --four-chips needs {PIPE_STAGES} TPU chips, found "
            f"{len(devices)}")
        return 1
    log(f"jax {jax.__version__} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"chip_smoke: the repo's src/repro is missing next to {__file__}")
        return 1
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    if args.four_chips:
        from repro.configs.registry import get_config
        run_phase("four-chips", phase_four_chips, get_config("phi3-mini-3.8b"),
                  args.seed)
    else:
        from repro.models.cnn import resnet50
        graph = resnet50(batch=1)
        params = graph.init(jax.random.PRNGKey(args.seed))
        run_phase("resnet50-raw", phase_resnet_raw, graph, params, args.seed)
        run_phase("resnet50-q8", phase_resnet_q8, graph, params, args.seed)
        run_phase("decode", phase_decode, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
