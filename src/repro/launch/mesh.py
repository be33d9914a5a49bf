"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain the placeholder devices.

Single pod: (16, 16) = 256 v5e chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); "pod" is pure
data parallelism across the DCI/ICI-linked pods (DEFER's independent chains).
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], **kw):
    """``jax.make_mesh`` with every axis ``Auto``: the meshes here leave
    sharding propagation to GSPMD inside ``jit`` (``jax.make_mesh``
    defaults to ``Explicit`` axes)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, auto, **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model: int | None = None):
    """Mesh over whatever devices exist (CPU tests / smoke runs)."""
    n = jax.device_count()
    model = model or 1
    return make_auto_mesh((n // model, model), ("data", "model"))


def make_pipeline_mesh(num_stages: int, *, multi_pod: bool = False):
    """DEFER pipeline mesh: the chain lives on the "stage" axis (the
    single-pod "model" axis re-labelled); data axes replicate chains."""
    if multi_pod:
        shape = (2, 512 // (2 * num_stages), num_stages)
        axes = ("pod", "data", "stage")
    else:
        shape = (256 // num_stages, num_stages)
        axes = ("data", "stage")
    return make_auto_mesh(shape, axes)
