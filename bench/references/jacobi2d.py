"""Plain reference of Polybench jacobi-2d: the new value of a point is
``coefficient`` times the sum of itself and its four neighbours, with
zero (Dirichlet) values outside the grid.  Written from that
definition in jax.numpy; it shares nothing with the program."""
import jax.numpy as jnp

def make_step(config: dict):
    c = float(config["coefficient"])

    def step(u):
        p = jnp.pad(u, 1)
        return c * (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
                    + p[1:-1, :-2] + p[1:-1, 2:])
    return step
