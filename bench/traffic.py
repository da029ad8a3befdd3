"""Traffic generation: everything a cell's run trains on, made from the seed.

The program receives only the arrays made here.  The generators are copies
of the program's own, kept beside the benchmark so that the yardstick stays
fixed when the program's versions change:

  * ``image_task``: the synthetic Gaussian-prototype image task of
    ``repro.data.synthetic.make_image_classification`` (the same
    distribution), drawn on the device in one jitted call instead of as
    float64 on the host;
  * ``dirichlet_partition``: ``repro.data.partition.dirichlet_partition``,
    line for line (label skew of Hsu et al.);
  * ``base_probs_from_data``: ``repro.core.availability.
    base_probs_from_data`` (the paper's p_i = <nu_i, phi>).

``make_task`` reads one traffic file and one configuration and returns the
whole input of a run: images on the device, the labels, the per-client
shards, the base availability probabilities, the model weights and the
PRNG keys.  The same seed gives the same task; every seed gives the same
population and shapes, so one compiled program serves them all.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def seed_key(seed: int, *salt: int):
    """A raw ``uint32[2]`` PRNG key, on the host, from any non-negative
    integer seed.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a large seed, so
    seeds 2**32 apart would collide; ``SeedSequence`` mixes all of them."""
    return np.random.SeedSequence([int(seed), *salt]).generate_state(
        2, dtype=np.uint32)


def dirichlet_partition(rng: np.random.Generator, labels: np.ndarray, m: int,
                        alpha: float = 0.1, min_per_client: int = 1):
    """Assign sample indices to m clients with Dirichlet(alpha) label skew.

    Returns (indices: list of m int arrays, nu: [m, C] realized label
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


def image_task(key, labels, *, n_classes, shape, margin, noise):
    """Images ``[n, *shape]`` f32 for the given labels: a Gaussian prototype
    per class (scale ``margin``) plus per-sample noise (scale ``noise``)."""
    import jax
    import jax.numpy as jnp

    d = int(np.prod(shape))

    @jax.jit
    def gen(key, labels):
        kp, kx = jax.random.split(key)
        protos = margin * jax.random.normal(kp, (n_classes, d))
        x = protos[labels] + noise * jax.random.normal(kx, (labels.shape[0], d))
        return x.reshape((labels.shape[0],) + tuple(shape))

    return gen(key, jnp.asarray(labels))


def init_cnn(key, model):
    """The CNN's weights, made on the device in one jitted call, in the
    program's parameter layout (``conv<j>``/``fc<j>``/``head`` with ``w``
    and ``b``): He-style normal weights scaled by fan-in**-0.5, zero
    biases."""
    import jax
    import jax.numpy as jnp

    H, W, C = model["input_shape"]
    channels, hidden = model["channels"], model["hidden"]
    n_classes = model["n_classes"]

    @jax.jit
    def init(key):
        ks = jax.random.split(key, len(channels) + len(hidden) + 1)
        params, cin, i, h, w = {}, C, 0, H, W
        for j, cout in enumerate(channels):
            params[f"conv{j}"] = dict(
                w=jax.random.normal(ks[i], (3, 3, cin, cout))
                * (9 * cin) ** -0.5,
                b=jnp.zeros((cout,)))
            cin, h, w, i = cout, h // 2, w // 2, i + 1
        din = h * w * cin
        for j, dout in enumerate(hidden):
            params[f"fc{j}"] = dict(
                w=jax.random.normal(ks[i], (din, dout)) * din ** -0.5,
                b=jnp.zeros((dout,)))
            din, i = dout, i + 1
        params["head"] = dict(
            w=jax.random.normal(ks[i], (din, n_classes)) * din ** -0.5,
            b=jnp.zeros((n_classes,)))
        return params

    return init(key)


@dataclasses.dataclass
class Task:
    """Everything one run trains on (made by ``make_task``)."""
    images: object          # [n, H, W, C] f32, on the device
    labels: np.ndarray      # [n] int32
    client_indices: List[np.ndarray]
    base_p: object          # [m] f32 base availability
    params: dict            # the CNN's initial weights
    state_key: np.ndarray   # FLState.rng (of seed replicate 0 on a mesh)
    data_key: np.ndarray    # the sampler's data key


def make_task(cfg: dict, traffic: dict, seed: int) -> Task:
    """Build a cell's inputs from its configuration, its traffic file and
    the seed."""
    import jax.numpy as jnp

    model, dep = cfg["model"], cfg["deployment"]
    data, avail = traffic["data"], traffic["availability"]
    m, batch = dep["m"], cfg["training"]["batch"]
    n = m * data["samples_per_client"]
    # The population (each client's labels, and so its shard and its base
    # availability) is the traffic's, the same in every run: the program
    # compiles base_p into the round as a constant, so a population that
    # changed with the seed would compile anew in every run.  The seed
    # draws the images, the weights and every random stream of the rounds.
    pop = int(data["population_seed"])
    rng = np.random.default_rng([pop, 0])
    labels = rng.integers(0, model["n_classes"], n).astype(np.int32)
    images = image_task(seed_key(seed, 1), labels,
                        n_classes=model["n_classes"],
                        shape=tuple(model["input_shape"]),
                        margin=data["margin"], noise=data["noise"])
    idx, nu = dirichlet_partition(rng, labels, m, alpha=data["alpha"],
                                  min_per_client=batch)
    if avail["base_p"] == "from_data":
        base_p = base_probs_from_data(seed_key(pop, 2),
                                      jnp.asarray(nu, jnp.float32))
    elif avail["base_p"] == "ones":
        base_p = jnp.ones((m,), jnp.float32)
    else:
        raise ValueError(f"unknown base_p rule {avail['base_p']!r}")
    return Task(images=images, labels=labels, client_indices=idx,
                base_p=base_p, params=init_cnn(seed_key(seed, 3), model),
                state_key=seed_key(seed, 4), data_key=seed_key(seed, 5))
