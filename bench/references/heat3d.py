"""Plain reference of the 7-point heat step ``u + c * laplacian(u)``,
where the laplacian is the sum of the six face neighbours minus six
times the point, with zero (Dirichlet) values outside the grid and
``c`` the configuration's ``coefficient``.  Written from that
definition in jax.numpy; it shares nothing with the program."""
import jax.numpy as jnp

def make_step(config: dict):
    c = float(config["coefficient"])

    def step(u):
        p = jnp.pad(u, 1)
        mid = p[1:-1, 1:-1, 1:-1]
        faces = (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
                 + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
                 + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])
        return mid + c * (faces - 6.0 * mid)
    return step
