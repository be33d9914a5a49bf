"""Optimizer, schedule, checkpointing, data pipeline."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.train import checkpoint as ckpt
from repro.train.optimizer import (OptConfig, apply_updates, global_norm,
                                   init_opt_state, schedule)


def test_schedule_shape():
    cfg = OptConfig(lr=1e-3, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in range(100)]
    assert lrs[0] < lrs[9]                       # warmup rises
    assert abs(lrs[10] - 1e-3) < 1e-9            # peak at end of warmup
    assert lrs[99] < lrs[50] < lrs[11]           # cosine decays
    assert lrs[99] >= 0.1 * 1e-3 - 1e-12         # floor


def test_grad_clip_caps_update():
    params = {"w": jnp.ones((4, 4))}
    huge = {"w": jnp.full((4, 4), 1e6)}
    state = init_opt_state(params)
    cfg = OptConfig(lr=1.0, clip_norm=1.0, warmup_steps=1, total_steps=10,
                    weight_decay=0.0)
    new, state, stats = apply_updates(params, huge, state, cfg)
    assert float(stats["grad_norm"]) > 1e5
    # post-clip Adam step magnitude is bounded by lr
    assert float(jnp.abs(new["w"] - params["w"]).max()) <= 1.0 + 1e-5


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(-100, 100), min_size=1, max_size=8))
def test_global_norm_matches_numpy(vals):
    tree = {"a": jnp.asarray(vals, jnp.float32)}
    np.testing.assert_allclose(float(global_norm(tree)),
                               np.linalg.norm(np.asarray(vals, np.float32)),
                               rtol=1e-5, atol=1e-5)


def test_checkpoint_roundtrip_and_sharding():
    tree = {"a": {"b": np.arange(1000, dtype=np.float32).reshape(10, 100)},
            "c": [np.ones(3, np.int32), np.zeros((2, 2), np.float64)]}
    with tempfile.TemporaryDirectory() as d:
        out = ckpt.save(d, 5, tree, shard_bytes=1024)   # force multi-shard
        assert len([f for f in os.listdir(out) if f.startswith("shard")]) > 1
        like = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        back = ckpt.restore(d, 5, like)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert ckpt.latest_step(d) == 5


def test_data_pipeline_determinism_and_sharding():
    from repro.data.pipeline import TokenStream
    a = list(next(TokenStream(100, 8, 16, seed=3)) for _ in range(1))[0]
    b = next(TokenStream(100, 8, 16, seed=3))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # shards partition the batch deterministically but differ from each other
    s0 = next(TokenStream(100, 8, 16, seed=3, shard=0, num_shards=2))
    s1 = next(TokenStream(100, 8, 16, seed=3, shard=1, num_shards=2))
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(s0["tokens"], s1["tokens"])
    # labels are next-token shifted
    assert a["labels"].shape == a["tokens"].shape


def test_data_has_learnable_structure():
    """A bigram table must beat uniform on the synthetic stream."""
    from repro.data.pipeline import TokenStream
    it = TokenStream(50, 16, 64, seed=0)
    hits = tot = 0
    for _ in range(5):
        b = next(it)
        # the dominant structure: next = cur + stride (mod V); check top-1
        # predictability via empirical delta histogram
        delta = (b["labels"] - b["tokens"]) % 50
        vals, counts = np.unique(delta, return_counts=True)
        hits += counts.max()
        tot += delta.size
    assert hits / tot > 0.10        # >> 1/50 uniform chance


def test_prefetcher_preserves_order():
    from repro.data.pipeline import Prefetcher
    out = list(Prefetcher(iter(range(20)), depth=4))
    assert out == list(range(20))


def test_end_to_end_training_loss_drops():
    from repro.configs.registry import get_smoke
    from repro.data.pipeline import make_lm_iter
    from repro.train.loop import train
    cfg = get_smoke("starcoder2-3b")
    it = make_lm_iter(cfg, batch=8, seq_len=32, seed=0)
    opt = OptConfig(lr=2e-3, warmup_steps=3, total_steps=25)
    _, _, hist = train(cfg, opt, it, num_steps=25, log_every=24)
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.3
