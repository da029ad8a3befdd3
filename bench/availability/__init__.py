"""The reference's availability processes, one module a ``kind``.

A traffic file's ``availability`` block names its ``kind``; the harness
hands every field of the block but ``base_p`` to the program's
``AvailabilityCfg``, and the reference follows the same process through
``bench/availability/<kind>.py``, written out from its definition with
none of the program's code.  A module gives ``Process(avail, base_p)``,
whose ``draw(key, t)`` is the ``[m]`` boolean mask of round ``t`` from the
round's availability key (rounds are drawn in order, from 0), and
``EXAMPLE``, the kind's fields for a small case in which the CPU test
holds the reference to the program's counts.  The fields a
kind reads are required in the traffic file, so that the reference never
assumes a default of the program's; ``delta_floor`` is absent where there
is no floor.

Every kind but ``markov`` is memoryless (``Memoryless``): client i is
available at round t when a uniform draw is under p_i f(t), clipped to
[0, 1], with f the kind's modulation.
"""
from __future__ import annotations

import importlib

import numpy as np


def load(kind: str):
    """The module of availability kind ``kind``."""
    return importlib.import_module(f"bench.availability.{kind}")


def make(avail: dict, base_p):
    """The process of a traffic file's ``availability`` block over the
    clients' base probabilities ``base_p`` [m]."""
    return load(avail["kind"]).Process(avail, base_p)


class Memoryless:
    """Client i is available at round t when ``uniform < p_i``, with
    p_i = p_i f(t) (``modulation``), every probability under the kind's
    cutoff set to 0 (``cut``), then raised to at least ``delta_floor``
    where the traffic states one, and clipped to [0, 1]."""

    def __init__(self, avail: dict, base_p):
        import jax
        import jax.numpy as jnp

        self.avail = avail
        floor = float(avail.get("delta_floor", 0.0))

        def probs(p, t):
            p = self.cut(p * self.modulation(jnp.asarray(t, jnp.float32)))
            if floor:
                p = jnp.clip(p, floor, 1.0)
            return jnp.clip(p, 0.0, 1.0)

        self.base_p = base_p
        self._probs = jax.jit(probs)
        self._below = jax.jit(lambda k, p: jax.random.uniform(k, p.shape) < p)

    def modulation(self, t):
        raise NotImplementedError

    def cut(self, p):
        return p

    def draw(self, key, t: int) -> np.ndarray:
        return np.asarray(self._below(key, self._probs(self.base_p, t)))
