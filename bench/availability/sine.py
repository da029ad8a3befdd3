"""``sine``: f(t) = gamma sin(2 pi t / period) + 1 - gamma, the paper's
non-stationary availability (Appendix J.3)."""
from bench.availability import Memoryless


EXAMPLE = dict(gamma=0.3, period=20)


def sine(avail, t):
    import jax.numpy as jnp

    gamma = avail["gamma"]
    return gamma * jnp.sin(2 * jnp.pi * t / avail["period"]) + (1 - gamma)


class Process(Memoryless):
    def modulation(self, t):
        return sine(self.avail, t)
