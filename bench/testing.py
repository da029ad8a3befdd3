"""Small-size runs of the benchmark's cells on the CPU, for its tests.

``tiny_cell`` keeps a cell's code path, limits and traffic kind and cuts
its shapes to what a test run can hold.  ``run_tiny`` drives the rest of a
run (set-up, window, check) without the look for a chip.  ``FAULTS`` plants
a broken timed path underneath, each as a ``pytest`` monkeypatch.

    python -m bench.testing <cell> [<fault> ...]

runs one cell this way (sound, then each named fault) and prints one JSON
line per run; the seed-mesh cell needs four devices, which the tests give
it with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` in a child
process.
"""
from __future__ import annotations

import json
import sys
import time

from bench import harness, models

SEED = 2**33 + 17     # a seed above 32 bits


def tiny_cell(name: str) -> harness.Cell:
    """The cell with its shapes cut to what a CPU test run can hold: the
    model module's ``tiny`` sizes, at most 12 clients, a cohort of 4, s = 2,
    batch 4, 20 samples a client and 2 rounds a chunk."""
    cell = harness.load_cell(name)
    cfg, tr = cell.cfg, cell.traffic
    models.of(cfg).tiny(cfg["model"])
    dep = cfg["deployment"]
    dep["m"] = min(dep["m"], 12)
    if dep["c_max"]:
        dep["c_max"] = 4
    cfg["training"].update(s=2, batch=4)
    tr["data"]["samples_per_client"] = min(
        tr["data"]["samples_per_client"], 20)
    tr["chunk_rounds"] = 2
    return cell


def run_tiny(cell: harness.Cell, seed: int = SEED) -> dict:
    import jax

    return harness.run(cell, seed, 0.2, False, time.perf_counter(),
                       jax.devices()[:cell.chips])


# -- planted faults -----------------------------------------------------------

def _state_unchanged(mp):
    """Every round returns the state it was given."""
    from repro.core import engine
    orig = engine.make_round_fn_with_frozen

    def make(*a, **k):
        rf = orig(*a, **k)

        def round_fn(state, frozen, batches):
            _, metrics = rf(state, frozen, batches)
            return state, metrics
        return round_fn
    mp.setattr(engine, "make_round_fn_with_frozen", make)


def _half_batch(mp):
    """Local SGD sees the first half of every batch; the loss is the mean
    over that half."""
    import jax
    from repro.core import engine
    orig = engine.local_sgd

    def local_sgd(trainable, frozen, batches, rng, **kw):
        half = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batches)
        return orig(trainable, frozen, half, rng, **kw)
    mp.setattr(engine, "local_sgd", local_sgd)


def _echo_dropped(mp):
    """The echo kernel aggregates as if every client's echo were 1."""
    import jax.numpy as jnp
    from repro.kernels.echo_aggregate import ops
    orig = ops.echo_aggregate_flat

    def echo_aggregate_flat(clients, x_end, g, mask, echo, eta_g, **kw):
        return orig(clients, x_end, g, mask, jnp.ones_like(echo), eta_g, **kw)
    mp.setattr(ops, "echo_aggregate_flat", echo_aggregate_flat)


def _one_chip_mesh(mp):
    """The seed mesh collapsed onto one device: all seeds on one chip."""
    import jax
    from jax.sharding import AxisType
    from repro.launch import mesh

    mp.setattr(mesh, "make_seed_mesh", lambda n, **k: jax.make_mesh(
        (1, 1, 1), ("seed", "pod", "data"), axis_types=(AxisType.Auto,) * 3,
        devices=jax.devices()[:1]))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "echo_dropped": _echo_dropped, "one_chip_mesh": _one_chip_mesh}


def main(argv):
    import pytest

    name, faults = argv[0], argv[1:]
    from repro.launch import compilecache
    for fault in [None] + faults:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(compilecache, "enable", lambda *a, **k: "")
            if fault:
                FAULTS[fault](mp)
            res = run_tiny(tiny_cell(name))
        print(json.dumps(dict(fault=fault, correct=res["correct"],
                              check=res["check"])), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
