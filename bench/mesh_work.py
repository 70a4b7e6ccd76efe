"""Bytes the halo exchange of a mesh cell moves, counted from the
cell's own settings.

As in ``work.py``, the count says what the algorithm needs, not what
the program happens to send.  For each fused block of ``sweeps`` steps
a chip needs its deep-halo box: its block widened by ``sweeps`` times
the stencil's radius along every sharded axis.  It receives the points
of that box that lie inside the grid and outside its own block.  These
are the faces, the corners between sharded axes, and the pieces from
neighbours more than one hop away where a block is narrower than the
deep halo.  Points beyond the grid's edge are the boundary's fill,
which no chip sends.
"""
from __future__ import annotations

import itertools
import math

#: Points a stencil reaches along each axis in one step.
RADIUS = {"heat3d": 1}   # the 7-point star of Polybench heat-3d


def shards(config: dict) -> list[int]:
    """Chips along each grid dim (1 where the dim is not sharded)."""
    return [1 if name is None else
            int(config["mesh"][config["mesh_axes"].index(name)])
            for name in config["grid_axes"]]


def exchange_points(config: dict, deep: int) -> int:
    """Points all chips receive in one exchange ``deep`` points deep."""
    if config["boundary"] == "periodic":
        raise ValueError("the count leaves out grid-edge fill; a periodic "
                         "grid wraps instead")
    grid = [int(n) for n in config["grid"]]
    split = shards(config)
    block = [n // k for n, k in zip(grid, split)]
    total = 0
    for chip in itertools.product(*(range(k) for k in split)):
        box = 1
        for i, b, n, k in zip(chip, block, grid, split):
            box *= (min((i + 1) * b + deep, n) - max(i * b - deep, 0)
                    if k > 1 else n)
        total += box - math.prod(block)
    return total


def exchange_bytes_per_call(config: dict, traffic: dict,
                            dtype: str | None = None) -> int:
    """Bytes all chips receive in one call's exchanges: a ``sweeps``-deep
    exchange per whole fused block, and one as deep as the remainder's
    steps for a last, narrower block."""
    import jax.numpy as jnp
    itemsize = jnp.dtype(dtype or config["dtype"]).itemsize
    radius = RADIUS[config["stencil"]]
    steps, sweeps = int(traffic["steps_per_call"]), int(config["sweeps"])
    points = (steps // sweeps) * exchange_points(config, sweeps * radius)
    if steps % sweeps:
        points += exchange_points(config, (steps % sweeps) * radius)
    return points * itemsize
