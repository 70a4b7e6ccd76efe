"""Mesh construction.  Functions, not module constants: importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model code places arrays
    with ``with_sharding_constraint`` and lets the partitioner resolve
    the rest (``jax.make_mesh`` otherwise makes ``Explicit`` axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The production mesh: 16x16 = 256 chips/pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_stencil_mesh(ndim: int, *, multi_pod: bool = False):
    """Grid-aligned mesh for the distributed stencil runtime.

    One mesh axis per (sharded) grid dimension, built over the same device
    set as the production mesh: the Casper block->slice assignment at
    cluster scale.
    """
    n = 512 if multi_pod else 256
    if ndim == 1:
        shape, axes = (n,), ("sx",)
    elif ndim == 2:
        shape, axes = (n // 16, 16), ("sx", "sy")
    else:
        shape = (8, 8, 8) if multi_pod else (4, 8, 8)
        axes = ("sx", "sy", "sz")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over locally available devices (tests / examples)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
