"""``staircase``: f(t) = 1 on the first half of each ``period`` and
``staircase_low`` on the second."""
from bench.availability import Memoryless


EXAMPLE = dict(period=6, staircase_low=0.4)


class Process(Memoryless):
    def modulation(self, t):
        import jax.numpy as jnp

        period = self.avail["period"]
        return jnp.where(jnp.mod(t, period) < period / 2, 1.0,
                         self.avail["staircase_low"])
