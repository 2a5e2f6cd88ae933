"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run sets XLA_FLAGS before importing jax to get 512
placeholder host devices; real launches get the same shapes from the TPU
runtime.

Single pod (v5e-256): (16, 16) = ("data", "model")
Two pods           : (2, 16, 16) = ("pod", "data", "model")
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes over ``devices`` (default: all)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Mesh axes carrying the batch (pod folds into data-parallel)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_host_mesh(n: int | None = None, name: str = "data"):
    """Small helper mesh over whatever devices exist (tests/examples)."""
    devs = jax.devices() if n is None else jax.devices()[:n]
    return make_mesh((len(devs),), (name,), devices=devs)


def make_sketch_mesh(n: int | None = None):
    """1-D mesh for row-sharding a sketch's (depth, width) register state
    (``repro.sketch``). Rows are hash-independent, so the sketch update runs
    with zero cross-device traffic; ``n`` must divide the sketch depth."""
    return make_host_mesh(n, name="rows")
