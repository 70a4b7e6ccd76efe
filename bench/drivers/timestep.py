"""Driver ``timestep``: back-to-back explicit time-stepping solves.

Each call is one whole solve of ``steps_per_call`` stencil steps through
the program's entry (``CasperEngine.run``); each solve starts from the
state the last one left, and the host waits for every result before it
starts the next.

The grid is made on the device from the seed.  After the window, the
plain reference checks the window's last solve: it advances that
solve's input (the program's own state, held through the call as the
chain holds it anyway) ``steps_per_call`` steps over the whole grid and
compares point by point with what the program returned; the widest gap
is the number compared.  The last solve is decided by the clock, so the
program cannot know which one is checked, and the check holds no grid
that the chain itself does not.
"""
from __future__ import annotations

import functools
import time

from bench import work


def make_key(seed: int):
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    the low 32 bits)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices, *,
                 reference, dtype: str | None = None):
        self.config = config
        self.steps = int(traffic["steps_per_call"])
        self.dtype = dtype or config["dtype"]
        self.seed = seed
        self.device = devices[0]
        self.reference = reference
        self.shape = tuple(int(n) for n in config["grid"])
        self.point_updates_per_call = work.point_updates_per_call(
            config, traffic)
        self.bytes_per_call = work.algorithmic_bytes_per_call(
            config, traffic, self.dtype)
        self.fn = self.grid = self.last_in = self.out = None

    def make_grid(self):
        """Uniform [0, 1) grid from the seed, made on the device in one
        jitted call and stored at the run's dtype."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        shape, dtype = self.shape, self.dtype

        @functools.partial(jax.jit,
                           out_shardings=SingleDeviceSharding(self.device))
        def make(key):
            return jax.random.uniform(key, shape, jnp.float32).astype(dtype)
        return make(make_key(self.seed))

    def setup(self) -> None:
        """Make the grid and warm up the one call shape the window uses
        (compiled, or read from the compilation cache).  The warm-up's
        result is the window's first input."""
        from repro.core import PAPER_STENCILS, CasperEngine
        cfg = self.config
        spec = PAPER_STENCILS[cfg["stencil"]].with_boundary(cfg["boundary"])
        eng = CasperEngine(spec, backend="pallas", sweeps=int(cfg["sweeps"]),
                           tile=cfg["tile"])
        self.fn = functools.partial(eng.run, iters=self.steps)
        self.grid = self.fn(self.make_grid()).block_until_ready()

    def window(self, seconds: float) -> list[tuple[float, float, float]]:
        """Chain calls until ``seconds`` have passed since the first
        dispatch; return each call's (dispatched, returned, ready) host
        times.  Keeps the last call's input and output for
        :meth:`check`."""
        from jax.profiler import TraceAnnotation
        fn, x = self.fn, self.grid
        self.grid = None
        records = []
        while True:
            t0 = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                y = fn(x)
            t1 = time.perf_counter()
            with TraceAnnotation("bench.block"):
                y.block_until_ready()
            t2 = time.perf_counter()
            records.append((t0, t1, t2))
            if t2 - records[0][0] >= seconds:
                break
            x = y
        self.fn = None
        self.last_in, self.out = x, y
        return records

    def check(self) -> dict:
        """Advance the last call's input by ``steps_per_call`` steps with
        the plain reference in float32 and compare with what the
        program returned: the widest gap of a point."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        step = self.reference.make_step(self.config)

        @functools.partial(jax.jit, static_argnums=2)
        def gap(u, got, n):
            want = lax.fori_loop(0, n, lambda _, v: step(v),
                                 u.astype(jnp.float32))
            return jnp.max(jnp.abs(got.astype(jnp.float32) - want))

        value = float(gap(self.last_in, self.out, self.steps))
        self.last_in = self.out = None
        return {"max_abs_gap": value}
