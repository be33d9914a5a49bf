"""A Mamba2 + attention hybrid (Granite-4.0-H form) through the serving
chain at a small size: prefill and decode logits of concurrent sessions,
served in cross-session waves from the slab, against the benchmark's plain
reference (a full forward pass, the recurrence token by token); a
slow-decay case whose prefill state must carry into decode; and the
``ssm_step`` kernel against ``mamba_decode`` on a slab."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.references import granite_4_0_h_micro as reference
from repro.configs.base import SSMConfig
from repro.kernels import ops
from repro.models import ssm
from repro.models.lm_graph import decode_lm_graph
from repro.runtime import InferenceEngine, TopologySpec
from repro.runtime.dispatcher import DispatcherCodecs
from repro.runtime.wire import K_CLOSE, K_OPEN, K_STEP, WireCodec

# Granite-4.0-H's keys at a small size: 4 layers (mamba, mamba, attention,
# mamba), so each of the 2 stages holds Mamba2 layers and stage 1 the
# attention too; heads of 16 (packed caches), a state of 16.
CFG = {
    "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "shared_intermediate_size": 96,
    "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 16,
    "max_position_embeddings": 160, "attention_multiplier": 0.25,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "rms_norm_eps": 1e-5,
}
SEED = 2**33 + 15
CODECS = DispatcherCodecs(data=WireCodec("raw", "none"),
                          weights=WireCodec("raw", "none"))
# Both sides compute in f32 on the CPU; they differ in the order of their
# sums (the chunked SSD against the token-by-token recurrence, a batched
# step against a full pass), so logits agree to f32 rounding: logits are
# about 0.1 in size, and 2e-5 is some 100 f32 ulps of the widest.
ATOL = 2e-5


def graph(use_kernel: bool = True):
    c = CFG
    return decode_lm_graph(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["shared_intermediate_size"],
        cache_len=c["max_position_embeddings"], use_kernel=use_kernel,
        layer_types=c["layer_types"], mlp_kind="gated_silu", rope=False,
        attn_scale=c["attention_multiplier"],
        embed_scale=c["embedding_multiplier"],
        residual_scale=c["residual_multiplier"],
        logits_scaling=c["logits_scaling"],
        ssm=SSMConfig(state_dim=c["mamba_d_state"], head_dim=c["mamba_d_head"],
                      expand=c["mamba_expand"], chunk=c["mamba_chunk_size"],
                      conv_width=c["mamba_d_conv"]))


def seeded_params(g, slow: bool = False):
    params = weights.init_params(weights.param_specs(g), SEED)
    if slow:
        # dt = softplus(dt_raw - 6) <= about 0.01 for |dt_raw| < 1.5, and
        # A = -exp(-1.5) = -0.22: decay exp(dt A) >= 0.997 a token
        for name, p in params.items():
            if name.endswith("_mamba"):
                p["A_log"] = jnp.full_like(p["A_log"], -1.5)
                p["dt_bias"] = jnp.full_like(p["dt_bias"], -6.0)
    return params


def submit(d, sid, kind, toks, pos):
    return d.submit(np.asarray([toks], np.int32), client_id=sid, session=sid,
                    session_pos=pos, session_kind=kind)


def serve_logits(g, params, seqs, prompts, stages=2):
    """Each session's logits through an engine: its prompt prefilled, then
    the rest of its sequence fed a token per step, all sessions stepping
    concurrently (their steps merge into waves).  Returns, per session,
    the logits at positions ``prompt - 1 .. len - 1``."""
    eng = InferenceEngine(g, TopologySpec.chain(g, stages), codecs=CODECS,
                          max_batch=4)
    eng.configure(params)
    eng.start()
    d = eng.dispatcher
    out = [None] * len(seqs)
    barrier = threading.Barrier(len(seqs))

    def one(i):
        sid, seq, n = f"h{i}", seqs[i], prompts[i]
        d.session_register(sid)
        try:
            rows = [np.asarray(submit(d, sid, K_OPEN, seq[:n], 0)
                               .result(60))[0, -1]]
            barrier.wait()
            for pos in range(n, len(seq)):
                rows.append(np.asarray(submit(d, sid, K_STEP, [seq[pos]], pos)
                                       .result(60))[0, -1])
            out[i] = np.stack(rows)
        finally:
            d.session_unregister(sid)
            submit(d, sid, K_CLOSE, [0], 0).result(10)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(seqs))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        waves = [n["totals"]["waves"] for n in eng.report().per_node]
        steps = [n["totals"]["step_rows"] for n in eng.report().per_node]
    finally:
        eng.shutdown()
    assert all(o is not None for o in out)
    return out, waves, steps


def reference_logits(params, seq, n):
    cfg = dict(CFG, max_position_embeddings=len(seq))
    return np.asarray(reference.logits(params, cfg, np.asarray(seq)))[n - 1:]


def test_hybrid_sessions_through_the_chain_match_the_reference():
    """Three sessions of different prompt lengths, prefilled and then
    stepped together through 2 stages: the served logits at every
    position are the reference's, and the steps were served in waves of
    more than one session."""
    g = graph()
    params = seeded_params(g)
    rng = np.random.default_rng(3)
    prompts = [5, 23, 40]
    seqs = [rng.integers(0, CFG["vocab_size"], n + 12).tolist()
            for n in prompts]
    served, waves, steps = serve_logits(g, params, seqs, prompts)
    for seq, n, got in zip(seqs, prompts, served):
        np.testing.assert_allclose(got, reference_logits(params, seq, n),
                                   atol=ATOL, rtol=0)
    assert all(s > w for s, w in zip(steps, waves))   # rows merged in waves


def test_slow_decay_state_carries_from_prefill_into_decode():
    """With the state decaying by less than 0.3% a token, what a 120-token
    prefill leaves in it still moves the decode logits: they match the
    reference over the whole sequence, and not a run of the same decode
    tokens with the prompt cut to its last 8 tokens."""
    g = graph()
    params = seeded_params(g, slow=True)
    rng = np.random.default_rng(4)
    n = 120
    seq = rng.integers(0, CFG["vocab_size"], n + 8).tolist()
    (got,), _, _ = serve_logits(g, params, [seq], [n])
    want = reference_logits(params, seq, n)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    cut = reference_logits(params, seq[n - 8:], 8)
    assert np.abs(want[1:] - cut[1:]).max() > 100 * ATOL


def test_plain_decoder_graph_is_unchanged_by_the_hybrid_options():
    """The defaults build the GELU decoder with RoPE: no Mamba2 node, and
    the same parameters as before the hybrid's options."""
    g = decode_lm_graph()
    assert [n.name for n in g.nodes] == ["embed", "blk0_attn", "blk0_mlp",
                                         "blk1_attn", "blk1_mlp", "head"]
    assert set(g["blk0_mlp"].param_spec) == {"ln", "up", "down"}


def _mamba_slab(rows=6, h=8, p=16, n=16, seed=0):
    s = ssm.MambaSpec(64, SSMConfig(state_dim=n, head_dim=p, expand=2,
                                    chunk=16, conv_width=4))
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    slab = {"conv": jax.random.normal(k[0], (rows, 3, s.conv_channels)),
            "ssd": jax.random.normal(k[1], (rows, h, p, n))}
    return s, slab


@pytest.mark.parametrize("slots", [[3, 0, 4, 5, 5], [1], [4, 2]])
def test_ssm_step_kernel_reads_and_writes_the_wave_rows_in_place(slots):
    """The kernel (interpreted) against ``mamba_decode``'s recurrence on
    the gathered rows, for slots out of order with the last row padding
    on the scratch row 5: the wave's rows come back updated, every other
    row of the slab unchanged."""
    s, slab = _mamba_slab()
    B = len(slots)
    h, p, n = 8, 16, 16
    k = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(k[0], (B, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, h)))
    A = -jnp.exp(0.3 * jax.random.normal(k[2], (h,)))
    Bm = jax.random.normal(k[3], (B, n))
    Cm = jax.random.normal(k[4], (B, n))
    sl = jnp.asarray(slots, jnp.int32)
    y, new = ops.ssm_step(slab["ssd"], sl, x, dt, A, Bm, Cm)
    want_y, want_s = ssm._ssd_update(slab["ssd"][sl], x, dt, A, Bm, Cm)
    old, new = np.asarray(slab["ssd"]), np.asarray(new)
    for i, r in enumerate(slots):
        if r == 5:
            continue
        np.testing.assert_allclose(y[i], want_y[i], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(new[r], want_s[i], rtol=1e-6, atol=1e-6)
    for r in set(range(6)) - set(slots):
        assert np.array_equal(new[r], old[r])


def test_mixer_on_slab_rows_matches_mamba_decode():
    """The whole mixer on slab rows (kernel and gathered paths) is
    ``mamba_decode`` on those rows, conv state included."""
    s, slab = _mamba_slab()
    g = graph()
    params = seeded_params(g)["blk0_mamba"]
    slots = jnp.asarray([4, 1, 5], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 1, 64))
    rows = {k: v[slots] for k, v in slab.items()}
    want, cache = ssm.mamba_decode(params, s, x, rows)
    for kernel in (False, True):
        out, new = ssm.mamba_mixer_slots(params, s, x, dict(slab), slots,
                                         use_kernel=kernel)
        np.testing.assert_allclose(x + out, want, rtol=1e-5, atol=1e-5)
        for name in ("conv", "ssd"):
            got = np.asarray(new[name])
            np.testing.assert_allclose(got[[4, 1]], cache[name][:2],
                                       rtol=1e-5, atol=1e-5)
            for r in (0, 2, 3):
                assert np.array_equal(got[r], np.asarray(slab[name][r]))
