"""Driver ``mesh_timestep``: the ``timestep`` traffic on a grid sharded
over the cell's chips.

Each call is one whole solve of ``steps_per_call`` stencil steps through
the program's mesh entry, ``CasperEngine.distributed_fn``, on a
``Mesh`` of the cell's devices shaped ``config["mesh"]`` with axes
``config["mesh_axes"]``; grid dim ``d`` is sharded over mesh axis
``config["grid_axes"][d]``.  The window is ``timestep``'s.

The grid is made on the devices from the seed, straight into its
sharding: it never passes through the host and no chip holds it whole.
The check advances the last solve's input with the configuration's
sharded plain reference (``references/<reference>.py``, whose
``make_step(config, mesh, grid_axes)`` steps the sharded grid and
exchanges its own halo), so that, like the program, it needs only each
chip's share of the grid; the widest gap of a point over the whole grid
is the number compared.
"""
from __future__ import annotations

import functools
import os

from bench import harness

_timestep = harness.load_module(
    "drivers", "timestep",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Driver(_timestep.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int, devices, *,
                 reference, dtype: str | None = None):
        super().__init__(config, traffic, seed, devices,
                         reference=reference, dtype=dtype)
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self.grid_axes = tuple(config["grid_axes"])
        self.mesh = Mesh(np.array(devices).reshape(config["mesh"]),
                         tuple(config["mesh_axes"]))
        self.sharding = NamedSharding(self.mesh, P(*self.grid_axes))

    def make_grid(self):
        """Uniform [0, 1) grid from the seed, each chip making its own
        share in one jitted call, stored at the run's dtype."""
        import jax
        import jax.numpy as jnp
        shape, dtype = self.shape, self.dtype

        @functools.partial(jax.jit, out_shardings=self.sharding)
        def make(key):
            return jax.random.uniform(key, shape, jnp.float32).astype(dtype)
        return make(_timestep.make_key(self.seed))

    def setup(self) -> None:
        """Make the grid and warm up the one call shape the window uses;
        the warm-up's result is the window's first input."""
        from repro.core import PAPER_STENCILS, CasperEngine
        cfg = self.config
        spec = PAPER_STENCILS[cfg["stencil"]].with_boundary(cfg["boundary"])
        eng = CasperEngine(spec, backend="pallas", sweeps=int(cfg["sweeps"]),
                           tile=cfg["tile"])
        self.fn = eng.distributed_fn(self.mesh, self.grid_axes,
                                     iters=self.steps)
        self.grid = self.fn(self.make_grid()).block_until_ready()

    def check(self) -> dict:
        """Advance the last call's input by ``steps_per_call`` steps with
        the sharded plain reference in float32 and compare with what the
        program returned: the widest gap of a point."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        step = self.reference.make_step(self.config, self.mesh,
                                        self.grid_axes)

        @functools.partial(jax.jit, static_argnums=2)
        def gap(u, got, n):
            want = lax.fori_loop(0, n, lambda _, v: step(v),
                                 u.astype(jnp.float32))
            return jnp.max(jnp.abs(got.astype(jnp.float32) - want))

        value = float(gap(self.last_in, self.out, self.steps))
        self.last_in = self.out = None
        return {"max_abs_gap": value}
