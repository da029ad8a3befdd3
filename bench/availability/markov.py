"""``markov``: a Gilbert-Elliott on/off chain per client that starts with
every client on.  Each round draws one uniform u a client: an on client
stays on when u > ``markov_down``; an off client turns on when u < up_i =
clip(``markov_up`` p_i / mean p, 0, 1).  A ``delta_floor`` raises up_i to
``floor * markov_down / (1 - floor)``, the turn-on rate whose stationary
share on is the floor."""
import numpy as np


EXAMPLE = dict(markov_up=0.3, markov_down=0.2)


class Process:
    def __init__(self, avail: dict, base_p):
        import jax
        import jax.numpy as jnp

        down = float(avail["markov_down"])
        floor = float(avail.get("delta_floor", 0.0))
        up = jnp.clip(avail["markov_up"] * base_p
                      / jnp.maximum(jnp.mean(base_p), 1e-6), 0.0, 1.0)
        if floor:
            up = jnp.clip(jnp.maximum(
                up, floor * down / max(1.0 - floor, 1e-6)), 0.0, 1.0)
        self.up = up
        self.on = jnp.ones(base_p.shape, bool)

        def step(key, on, up):
            u = jax.random.uniform(key, on.shape)
            return jnp.where(on, u > down, u < up)

        self._step = jax.jit(step)

    def draw(self, key, t: int) -> np.ndarray:
        self.on = self._step(key, self.on, self.up)
        return np.asarray(self.on)
