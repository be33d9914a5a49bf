"""Pallas TPU kernels for the hot spots of the DEFER chain.

Each kernel has a pure-jnp oracle in ``ref.py``; ``ops.py`` holds the
jit'd wrappers the models call.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter on this backend.

    Only the CPU interprets (the test suite's bit-faithful validation
    path); a TPU compiles the kernels with Mosaic.  Any other backend is
    refused rather than silently interpreted: a run that lost its chip
    must fail, not pass slowly.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels need a TPU (compiled) or the CPU (interpreted); "
        f"JAX's default backend is {backend!r}")
