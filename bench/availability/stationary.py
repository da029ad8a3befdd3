"""``stationary``: f(t) = 1, each client available with its base
probability every round."""
from bench.availability import Memoryless


EXAMPLE = {}


class Process(Memoryless):
    def modulation(self, t):
        import jax.numpy as jnp

        return jnp.ones_like(t)
