"""The work a call does, counted from the cell's own settings.

These counts belong to the benchmark and not to the program: they say
what the algorithm needs, not what today's kernel happens to move, so
a kernel that over-fetches shows a lower roofline share instead of a
higher byte count.
"""
from __future__ import annotations

import math


def points(config: dict) -> int:
    return math.prod(int(n) for n in config["grid"])


def blocks_per_call(config: dict, traffic: dict) -> int:
    """Fused blocks of one call: ``ceil(steps_per_call / sweeps)`` (the
    last one narrower when ``sweeps`` does not divide the steps)."""
    return -(-int(traffic["steps_per_call"]) // int(config["sweeps"]))


def point_updates_per_call(config: dict, traffic: dict) -> int:
    """Grid points times stencil steps of one call."""
    return points(config) * int(traffic["steps_per_call"])


def algorithmic_bytes_per_call(config: dict, traffic: dict,
                               dtype: str | None = None) -> int:
    """Least HBM bytes of one call: per fused block one read and one
    write of the grid, the least any kernel computing the block's
    output from its input must move.  A kernel fused across blocks would need a new count."""
    import jax.numpy as jnp
    itemsize = jnp.dtype(dtype or config["dtype"]).itemsize
    return blocks_per_call(config, traffic) * 2 * points(config) * itemsize
