"""Client models of the benchmark, one module a ``model.kind``.

A configuration's ``model.kind`` names ``bench/models/<kind>.py``, which
the harness imports by that name; a new kind is a new file.  A module
gives:

``CLIENT_BLOCK``
    clients one call of the reference's local SGD takes (one compiled
    shape; the reference pads the last block).
``groups(rng, model, n) -> int32 [n]``
    each sample's group (a class, a topic), drawn from the population's
    ``numpy`` generator; the Dirichlet partition and the base
    availability are made from these in ``traffic.py``.
``arrays(key, groups, model, data) -> {name: [n, ...]}``
    the per-sample arrays the program's device store holds and its loss
    reads, made from the run seed's key (on the device where large).
``init(key, model) -> params``
    the initial weights, on the device, in one jitted call.
``split(params, model) -> (trainable, frozen)``
    what federated training moves and the base it leaves fixed; a frozen
    tree with leaves reaches the program as a runtime argument.
``loss_fn(model)``
    the program's ``loss_fn(trainable, frozen, batch, rng) -> scalar``.
``reference_loss(model, params, frozen, batch, precision) -> scalar``
    the same loss in plain ``jax.numpy`` with matrix products at
    ``precision``, none of the program's code.
``train_flops_per_sample(model) -> int``
    the FLOPs of one sample's forward and backward pass (``mfu``).
``tiny(model)``
    cuts the model's sizes in place to what a CPU test run can hold.
"""
from __future__ import annotations

import importlib


def load(kind: str):
    """The module of model kind ``kind``: ``bench.models.<kind>``."""
    return importlib.import_module(f"bench.models.{kind}")


def of(cfg: dict):
    """The model module of a configuration."""
    return load(cfg["model"]["kind"])
