"""Plain reference of ResNet50 as ``bench/configs/resnet50.json`` states it.

Straight ``jax.numpy``/``lax``, no batching machinery, no wire, no
partition: the whole network on one array of images.  It takes the
benchmark's weights (``bench/weights.py``), keyed as the program names
its layers (``stem``, ``s<stage>b<block>_c1|c2|c3|sc``, ``fc``), and
imports nothing of the program.

``precision`` says how it computes:

- ``float32``: every convolution and matmul at ``highest`` precision (on a
  TPU the default rounds f32 operands to bf16): the reference;
- ``bfloat16``: weights and activations in bf16 at the default precision;
- ``int8``: f32 arithmetic on operands rounded to int8, each convolution's
  and matmul's input and weight by its own absmax scale (symmetric, 127
  steps): one step below the bf16 operands the program's f32 convolutions
  take at XLA's default TPU precision (activations by each image's
  absmax).

The last two are controls (``bench/check.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 16      # images per reference call


def int8_round(a, per_sample: bool):
    """``a`` rounded to 127 symmetric steps of its absmax: per sample
    (over every axis but the first) for activations, whole for weights."""
    axes = tuple(range(1, a.ndim)) if per_sample else None
    s = jnp.maximum(jnp.abs(a).max(axis=axes, keepdims=per_sample),
                    1e-30) / 127.0
    return jnp.round(a / s) * s


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _forward(params, x, *, cfg, rnd):
    def conv_bn(name, x, stride, relu):
        p = params[name]
        y = _conv(rnd(x, True), rnd(p["w"], False), stride) * p["scale"] \
            + p["bias"]
        return jax.nn.relu(y) if relu else y

    x = conv_bn("stem", x, 2, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, blocks in enumerate(cfg["stage_blocks"]):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            base = f"s{si}b{bi}"
            y = conv_bn(f"{base}_c1", x, 1, True)
            y = conv_bn(f"{base}_c2", y, stride, True)
            y = conv_bn(f"{base}_c3", y, 1, False)
            sc = conv_bn(f"{base}_sc", x, stride, False) if bi == 0 else x
            x = jax.nn.relu(y + sc)
    x = x.mean(axis=(1, 2))
    return rnd(x, True) @ rnd(params["fc"]["w"], False) + params["fc"]["b"]


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: tuple, int8: bool):
    rnd = int8_round if int8 else (lambda a, per_sample: a)
    return jax.jit(functools.partial(_forward, cfg=dict(cfg_items), rnd=rnd))


def logits(params, cfg: dict, images: np.ndarray, precision="float32"
           ) -> np.ndarray:
    """Class logits of ``images`` [N, H, W, C], in blocks of ``BLOCK``."""
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    fwd = _compiled(tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                                 for k, v in cfg.items()
                                 if isinstance(v, (int, float, str, list)))),
                    precision == "int8")
    out = []
    with jax.default_matmul_precision(
            "default" if precision == "bfloat16" else "highest"):
        for i in range(0, len(images), BLOCK):
            chunk = images[i:i + BLOCK]
            pad = BLOCK - len(chunk)
            x = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:],
                                                chunk.dtype)]) if pad else chunk
            y = fwd(p, jnp.asarray(x, dtype))
            out.append(np.asarray(y, np.float32)[:len(chunk)])
    return np.concatenate(out)
