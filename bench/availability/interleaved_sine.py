"""``interleaved_sine``: the sine's p_i f(t), with every probability under
``cutoff`` set to exactly 0 (clients that cannot be reached at all in
those rounds)."""
from bench.availability import Memoryless
from bench.availability.sine import sine


EXAMPLE = dict(gamma=0.5, period=10, cutoff=0.2)


class Process(Memoryless):
    def modulation(self, t):
        return sine(self.avail, t)

    def cut(self, p):
        import jax.numpy as jnp

        return jnp.where(p >= self.avail["cutoff"], p, 0.0)
