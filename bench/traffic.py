"""Traffic generation: everything a cell's run trains on, made from the seed.

The program receives only the arrays made here.  What a sample holds, and
the weights, come from the configuration's model module
(``bench/models/<kind>.py``); what every model shares is here, as copies of
the program's own generators, kept beside the benchmark so that the
yardstick stays fixed when the program's versions change:

  * ``dirichlet_partition``: ``repro.data.partition.dirichlet_partition``,
    line for line (group skew of Hsu et al.);
  * ``base_probs_from_data``: ``repro.core.availability.
    base_probs_from_data`` (the paper's p_i = <nu_i, phi>).

``make_task`` reads one traffic file and one configuration and returns the
whole input of a run: the per-sample arrays, each sample's group, the
per-client shards, the base availability probabilities, the model's
trainable weights and frozen base, and the PRNG keys.  The same seed gives
the same task; every seed gives the same population, availability draws
and shapes, so one compiled program serves them all and each does the same
work.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench import models


def seed_key(seed: int, *salt: int):
    """A raw ``uint32[2]`` PRNG key, on the host, from any non-negative
    integer seed.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a large seed, so
    seeds 2**32 apart would collide; ``SeedSequence`` mixes all of them."""
    return np.random.SeedSequence([int(seed), *salt]).generate_state(
        2, dtype=np.uint32)


def dirichlet_partition(rng: np.random.Generator, labels: np.ndarray, m: int,
                        alpha: float = 0.1, min_per_client: int = 1):
    """Assign sample indices to m clients with Dirichlet(alpha) skew over
    the samples' groups (``labels``: a group id a sample).

    Returns (indices: list of m int arrays, nu: [m, C] realized group
    distribution per client)."""
    labels = np.asarray(labels)
    C = int(labels.max()) + 1
    by_class = [rng.permutation(np.where(labels == c)[0]) for c in range(C)]
    nu = rng.dirichlet(np.full(C, alpha), size=m)
    client_idx = [[] for _ in range(m)]
    for c in range(C):
        n_c = len(by_class[c])
        if n_c == 0:
            continue
        w = nu[:, c] / max(nu[:, c].sum(), 1e-12)
        counts = np.floor(w * n_c).astype(int)
        counts[np.argmax(counts)] += n_c - counts.sum()
        splits = np.cumsum(counts)[:-1]
        for i, part in enumerate(np.split(by_class[c], splits)):
            client_idx[i].append(part)
    out = []
    for i in range(m):
        idx = np.concatenate(client_idx[i]) if client_idx[i] else \
            np.zeros((0,), np.int64)
        if len(idx) < min_per_client:
            extra = rng.integers(0, len(labels), min_per_client - len(idx))
            idx = np.concatenate([idx, extra])
        out.append(rng.permutation(idx))
    realized = np.zeros((m, C))
    for i in range(m):
        if len(out[i]):
            bc = np.bincount(labels[out[i]], minlength=C)
            realized[i] = bc / bc.sum()
    return out, realized


def base_probs_from_data(key, nu):
    """nu: [m, C] per-client label distributions -> p [m] in (0, 1]."""
    import jax
    import jax.numpy as jnp

    m, C = nu.shape
    half = C // 2
    scales = jnp.concatenate([jnp.ones(half), 0.5 * jnp.ones(C - half)])
    phi = jax.random.uniform(key, (C,)) * scales
    p = jnp.dot(nu, phi, precision=jax.lax.Precision.HIGHEST)
    return jnp.clip(p, 1e-3, 1.0)


@dataclasses.dataclass
class Task:
    """Everything one run trains on (made by ``make_task``)."""
    arrays: dict            # {name: [n, ...]} per-sample arrays of the model
    groups: np.ndarray      # [n] int32 each sample's group
    client_indices: List[np.ndarray]
    base_p: object          # [m] f32 base availability
    params: dict            # the initial trainable weights
    frozen: dict            # the frozen base ({} where everything trains)
    state_key: np.ndarray   # FLState.rng (of seed replicate 0 on a mesh),
                            # from the population seed
    data_key: np.ndarray    # the sampler's data key

    def free_arrays(self):
        """Drop the per-sample arrays, deleting those on the device."""
        for v in self.arrays.values():
            if hasattr(v, "delete"):
                v.delete()
        self.arrays = None


def sample_arrays(cfg: dict, traffic: dict, seed: int, groups) -> dict:
    """The per-sample arrays of ``make_task``, made again from the seed."""
    return models.of(cfg).arrays(seed_key(seed, 1), groups, cfg["model"],
                                 traffic["data"])


def make_task(cfg: dict, traffic: dict, seed: int) -> Task:
    """Build a cell's inputs from its configuration, its traffic file and
    the seed."""
    import jax.numpy as jnp

    model, dep = cfg["model"], cfg["deployment"]
    mod = models.of(cfg)
    data, avail = traffic["data"], traffic["availability"]
    m, batch = dep["m"], cfg["training"]["batch"]
    n = m * data["samples_per_client"]
    # The population (each sample's group, and so each client's shard and
    # its base availability) is the traffic's, the same in every run: the
    # program compiles base_p into the round as a constant, so a population
    # that changed with the seed would compile anew in every run.  So are
    # the rounds' availability draws (the FL state's key): which clients
    # train, and so how many rows a dense round computes, would otherwise
    # change the work from seed to seed.  The seed draws the samples, the
    # weights and the batches.
    pop = int(data["population_seed"])
    rng = np.random.default_rng([pop, 0])
    groups = mod.groups(rng, model, n)
    arrays = sample_arrays(cfg, traffic, seed, groups)
    idx, nu = dirichlet_partition(rng, groups, m, alpha=data["alpha"],
                                  min_per_client=batch)
    if avail["base_p"] == "from_data":
        base_p = base_probs_from_data(seed_key(pop, 2),
                                      jnp.asarray(nu, jnp.float32))
    elif avail["base_p"] == "ones":
        base_p = jnp.ones((m,), jnp.float32)
    else:
        raise ValueError(f"unknown base_p rule {avail['base_p']!r}")
    params, frozen = mod.split(mod.init(seed_key(seed, 3), model), model)
    return Task(arrays=arrays, groups=groups, client_indices=idx,
                base_p=base_p, params=params, frozen=frozen,
                state_key=seed_key(pop, 4), data_key=seed_key(seed, 5))
