"""Plain reference of the 7-point heat step (``heat3d.py``) on a grid
sharded over a mesh, for grids that one chip cannot check whole.

Each shard takes ``heat3d.make_step``'s step of its own block, whose
zero padding stands for the neighbours, and then adds ``c`` times the
plane each neighbour holds next to it: one plane per side of every
sharded axis, sent with ``lax.ppermute``.  A shard at the grid's edge
receives zeros there, which is the zero boundary.  The step is linear
in the neighbours, so this is the step of the whole grid.  One plane is
exchanged per step, and no padded copy of the grid is made; it shares
nothing with the program.
"""
import os

import jax
from jax import lax
from jax.sharding import PartitionSpec as P


def _heat3d():
    from bench import harness
    return harness.load_module("references", "heat3d",
                               os.path.dirname(os.path.dirname(
                                   os.path.abspath(__file__))))


def _neighbour_planes(u, axis: int, name: str, n: int):
    """The planes the left and right neighbours along ``name`` hold next
    to this shard's block (zeros at the grid's edge)."""
    size = u.shape[axis]
    first = lax.slice_in_dim(u, 0, 1, axis=axis)
    last = lax.slice_in_dim(u, size - 1, size, axis=axis)
    from_left = lax.ppermute(last, name, [(i, i + 1) for i in range(n - 1)])
    from_right = lax.ppermute(first, name, [(i + 1, i) for i in range(n - 1)])
    return from_left, from_right


def make_step(config: dict, mesh, grid_axes):
    """The heat step of the whole grid, as a function of the global
    array sharded ``P(*grid_axes)`` over ``mesh``."""
    if config["boundary"] != "zero":
        raise ValueError("the sharded heat3d reference has a zero boundary")
    c = float(config["coefficient"])
    local_step = _heat3d().make_step(config)
    sharded = [(d, name, mesh.shape[name])
               for d, name in enumerate(grid_axes) if name is not None]

    def local(u):
        v = local_step(u)
        for d, name, n in sharded:
            from_left, from_right = _neighbour_planes(u, d, name, n)
            size = u.shape[d]
            v = lax.dynamic_update_slice_in_dim(
                v, lax.slice_in_dim(v, 0, 1, axis=d) + c * from_left, 0, d)
            v = lax.dynamic_update_slice_in_dim(
                v, lax.slice_in_dim(v, size - 1, size, axis=d)
                + c * from_right, size - 1, d)
        return v

    spec = P(*grid_axes)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec)
