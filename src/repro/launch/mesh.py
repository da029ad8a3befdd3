"""Production mesh construction.

Functions (never module-level constants) so importing this module never
touches jax device state. Target hardware: TPU v5e, 256 chips per pod;
multi-pod = 2 pods = 512 chips over DCN.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

# v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link


def _auto_mesh(shape, axes, devices):
    """Mesh whose axes are all ``Auto``: the executors place arrays with
    explicit ``NamedSharding``s and let the compiler propagate the rest.
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which ``vmap``
    rejects inputs whose mapped axis carries different mesh axes (the seed
    executor's sharded states beside its replicated data keys)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for {axes}={shape}, have {len(devs)} — "
            "run under launch/dryrun.py which forces 512 host devices")
    return _auto_mesh(shape, axes, devs[:n])


def make_test_mesh(*, multi_pod: bool = False):
    """Miniature mesh for CI: (2,2) or (2,2,1)... kept shape-compatible
    with the production axis names."""
    shape = (2, 2, 1) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return _auto_mesh(shape, axes, devs[:n])


def seed_mesh_shape(n_seeds: int, n_devices: int, *, multi_pod: bool = False):
    """Auto-size a ('seed', 'pod', 'data') mesh, or None when it cannot fit.

    The seed axis must be a DIVISOR of ``n_seeds`` (so an ``[S, ...]``
    state shards evenly; size 1 degenerates to replicated seeds).  Among
    the divisors that fit beside the pod axis, pick the one that uses the
    most devices — ``seed * pods * (devices // (seed * pods))`` — with
    the larger seed axis breaking ties (more seed parallelism at equal
    utilization): e.g. S=4 on 6 single-pod devices gives (2, 1, 3), all
    six chips, not (4, 1, 1).  Returns ``None`` exactly when even the
    pod axis alone exceeds the device count — the caller then degrades
    to the standard 2-/3-axis mesh and seeds ride the client axes
    instead (``sharding/rules.seed_pspecs(seed_axes=('pod','data'))``,
    the PR 4 placement).
    """
    assert n_seeds >= 1 and n_devices >= 0
    pods = 2 if multi_pod else 1
    if pods > n_devices:
        return None
    s_ax = max((d for d in range(1, n_seeds + 1)
                if n_seeds % d == 0 and d * pods <= n_devices),
               key=lambda d: (d * pods * (n_devices // (d * pods)), d))
    return (s_ax, pods, n_devices // (s_ax * pods))


def make_seed_mesh(n_seeds: int, *, multi_pod: bool = False,
                   test: bool = False):
    """('seed', 'pod', 'data') mesh for the S-batched grid executor.

    The dedicated seed axis is pure data parallelism over independent
    replicates — with it, the per-seed client placement survives
    (``seed_pspecs(seed_axes='seed')`` does not strip the inner
    ('pod','data') axes).  Sized by ``seed_mesh_shape`` (the divisor of
    S using the most devices; when S·pods exceeds the device count the
    seed axis shrinks), and when even the pod axis does not fit this degrades
    gracefully to the current 2-/3-axis mesh (``make_test_mesh`` /
    ``make_production_mesh``) — callers detect which mesh they got via
    ``'seed' in mesh.axis_names``.  ``test`` caps the mesh at 8 chips for
    CI (mirroring ``make_test_mesh``'s miniature tier).
    """
    devs = jax.devices()
    budget = min(len(devs), 8) if test else len(devs)
    shape = seed_mesh_shape(n_seeds, budget, multi_pod=multi_pod)
    if shape is None:
        return (make_test_mesh(multi_pod=multi_pod) if test
                else make_production_mesh(multi_pod=multi_pod))
    return _auto_mesh(shape, ("seed", "pod", "data"),
                      devs[:math.prod(shape)])


def mesh_axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def n_chips(mesh):
    return int(mesh.devices.size)

