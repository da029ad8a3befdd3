"""Logical-axis sharding rules -> PartitionSpec pytrees.

Mesh axes (launch/mesh.py):
  single pod: ('data', 'model') = (16, 16)
  multi-pod:  ('pod', 'data', 'model') = (2, 16, 16)

Logical mapping:
  clients            -> ('pod', 'data')        client-stacked FL state
  model-parallel dim -> 'model'                heads / d_ff / experts / vocab
  FSDP dim           -> 'data'                 lora-mode frozen base weights
  serve batch        -> 'data'                 (falls back to sequence
  KV-cache sequence  -> 'model' (+'data')       sharding when batch is tiny)

Specs are derived from leaf *path names* against the abstract parameter
tree, with divisibility checks against the actual mesh sizes; everything
that cannot be shard-mapped cleanly stays replicated, which is always
correct (XLA only needs consistent specs, not maximal ones).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _div(n, size):
    return size > 0 and n % size == 0


def _leaf_name(path):
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def _in_stack(path):
    return any(getattr(p, "key", None) == "stack" for p in path)


def _base_spec(name, shape, ax):
    """PartitionSpec for a 'bare' (unstacked) parameter leaf.  On a mesh
    without a 'model' axis (the ('seed','pod','data') grid mesh) every
    would-be model-parallel dim stays replicated (``_div(n, 0)`` is
    False), which is always correct."""
    md = ax.get("model", 0)

    def m(dim):
        return "model" if _div(shape[dim], md) else None

    if name in ("embed",):
        # vocab-parallel when divisible; else shard the embedding dim
        return P(m(0), None) if _div(shape[0], md) else P(None, m(1))
    if name in ("unembed",):
        return P(None, m(1)) if _div(shape[1], md) else P(m(0), None)
    if name in ("wq", "wk", "wv", "wi", "wi_s", "in_proj", "wq_x", "wk_x",
                "wv_x"):
        return P(None, m(1))
    if name in ("wo", "wd", "wd_s", "out_proj", "wo_x"):
        return P(m(0), None)
    if name in ("wi_e",):  # [E, d, 2*eff]
        if _div(shape[0], md):
            return P("model", None, None)
        return P(None, None, m(2))
    if name in ("wd_e",):  # [E, eff, d]
        if _div(shape[0], md):
            return P("model", None, None)
        return P(None, m(1), None)
    if name.startswith("b_"):  # lora B: [r, out]
        return P(None, m(1))
    # router, norms, lora A, conv, ssm scalars, biases -> replicated
    return P(*([None] * len(shape)))


def _fsdp_augment(spec, shape, ax, min_size=1 << 20):
    """Add 'data' sharding on the largest still-unsharded dim (frozen base
    weights in lora mode — ZeRO-3 style)."""
    if int(np.prod(shape)) < min_size:
        return spec
    dd = ax.get("data", 1)
    best, best_dim = 0, None
    for i, (s, sp) in enumerate(zip(shape, tuple(spec) + (None,) * len(shape))):
        if sp is None and _div(s, dd) and s > best:
            best, best_dim = s, i
    if best_dim is None:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    parts[best_dim] = "data"
    return P(*parts)


def param_pspecs(cfg, mesh, params_shape, *, fsdp=False, mode="tp"):
    """Specs for a bare params tree (as from init_params).

    params_shape: jax.eval_shape result for init_params.
    fsdp: additionally shard big leaves over 'data' (lora frozen base).
    mode: 'tp' (tensor-parallel blocks, baseline) or 'dp' (replicate block
    weights over 'model' and let the within-client batch take that axis —
    the §Perf data-parallel variant; embeddings stay model-sharded).
    """
    ax = _axis_sizes(mesh)

    def f(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        core = shape[1:] if _in_stack(path) else shape
        if mode == "dp" and name not in ("embed", "unembed"):
            spec = P(*([None] * len(core))) if core else P()
        else:
            spec = _base_spec(name, core, ax) if core else P()
        if fsdp:
            spec = _fsdp_augment(spec, core, ax)
        if _in_stack(path):
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(f, params_shape)


def client_stack_pspecs(cfg, mesh, trainable_shape, *, multi_pod=False,
                        mode="tp"):
    """Client-stacked trainables: leading client axis over ('pod','data')."""
    ax = _axis_sizes(mesh)
    client_axes = _client_axes(ax, multi_pod)
    base = param_pspecs(cfg, mesh, trainable_shape, mode=mode)

    def add_client(spec_leaf):
        return P(client_axes, *spec_leaf)

    return jax.tree.map(add_client, base,
                        is_leaf=lambda x: isinstance(x, P))


def _client_axes(ax, multi_pod):
    return ("pod", "data") if (multi_pod and "pod" in ax) else ("data",)


def flat_pspecs(mesh, state_sds, *, multi_pod=False):
    """FLState-shaped PartitionSpec tree for the flat substrate.

    The dominant [m, N] buffers — the client stack and any model-shaped
    strategy memory (MIFA/FedVARP) — shard their client axis over
    ('pod','data'); the [N] global (and [N] server memory like FedAWE-M's
    velocity) stays replicated so the fused flat aggregation lowers to the
    implicit-gossip all-reduce; per-client [m] vectors (tau, markov,
    scalar strategy statistics) follow the client axis.

    ``state_sds``: ``jax.eval_shape`` of ``init_fl_state`` with
    ``flat_state=True``.  Returns a pytree with the same treedef (the
    static ``spec`` metadata rides along unchanged), ready for
    ``NamedSharding`` wrapping as the chunk jit's in/out shardings.
    """
    ax = _axis_sizes(mesh)
    ca = _client_axes(ax, multi_pod)
    m = int(state_sds.tau.shape[0])

    def leaf(x):
        shape = tuple(int(d) for d in x.shape)
        if len(shape) == 2 and shape[0] == m:
            return P(ca, None)           # [m, N] client-stacked
        if shape == (m,):
            return P(ca)                 # per-client vector
        return P(*([None] * len(shape)))  # global [N] / scalars / rng

    def fault_leaf(x):
        # fault-injection carry (core/faults.py): the [T, m] replay trace
        # shards its CLIENT (trailing) axis, [m] cluster labels follow tau
        shape = tuple(int(d) for d in x.shape)
        if shape == (m,):
            return P(ca)
        if len(shape) == 2 and shape[1] == m:
            return P(None, ca)
        return P(*([None] * len(shape)))

    def stale_leaf(x):
        # semi-async carry (core/staleness.py): the [tau_max, m, N] pending
        # ring buffer and the [tau_max, m] ages / [T, m] delay trace shard
        # their CLIENT (middle/trailing) axis, like the client stack does
        shape = tuple(int(d) for d in x.shape)
        if len(shape) == 3 and shape[1] == m:
            return P(None, ca, None)
        if len(shape) == 2 and shape[1] == m:
            return P(None, ca)
        if shape == (m,):
            return P(ca)
        return P(*([None] * len(shape)))

    fault = getattr(state_sds, "fault", None)
    stale = getattr(state_sds, "stale", None)
    return type(state_sds)(
        global_tr=P(None),
        clients_tr=(None if state_sds.clients_tr is None
                    else P(ca, None)),
        tau=P(ca),
        t=P(),
        extra=jax.tree.map(leaf, state_sds.extra),
        markov=P(ca),
        rng=P(None),
        spec=state_sds.spec,
        fault=None if fault is None else jax.tree.map(fault_leaf, fault),
        stale=None if stale is None else jax.tree.map(stale_leaf, stale),
    )


def cohort_pspecs(mesh, c_max, *, multi_pod=False):
    """PartitionSpecs for the sparse cohort working set (core/cohort.py).

    Returns ``dict(rows=P(ca, None), idx=P(ca), mask=P(ca))``: the
    gathered ``[c_max, N]`` f32 working rows shard their cohort axis over
    the client mesh axes exactly like the resident ``[m, N]`` stack — the
    gather/scatter is then a client-axis all-to-all and the cohort-local
    reductions lower to the same implicit-gossip all-reduce as the dense
    flat path — while ``[c_max]`` index/mask vectors follow along.
    ``c_max`` must divide the client mesh extent or the working set stays
    replicated (always correct, just unsharded)."""
    ax = _axis_sizes(mesh)
    ca = _client_axes(ax, multi_pod)
    extent = 1
    for a in ca:
        extent *= ax.get(a, 1)
    if not _div(int(c_max), extent):
        return dict(rows=P(None, None), idx=P(None), mask=P(None))
    return dict(rows=P(ca, None), idx=P(ca), mask=P(ca))


def sampler_pspecs(mesh, sampler_sds, m, *, multi_pod=False):
    """SamplerState-shaped PartitionSpec tree for the stateful device
    sampler (data/federated.make_device_sampler).

    Per-client buffers follow the client mesh axes — the ``[m, cap]``
    epoch-permutation matrix shards like the ``[m, N]`` client stack and
    the ``[m]`` cursor/epoch vectors like tau — while anything not
    client-leading (the carried PRNG key, scalars) stays replicated.
    ``sampler_sds``: ``jax.eval_shape`` of ``init_sampler_state``; the
    uniform sampler's empty state yields an empty spec tree.
    """
    ax = _axis_sizes(mesh)
    ca = _client_axes(ax, multi_pod)

    def leaf(path, x):
        shape = tuple(int(d) for d in x.shape)
        # the carried reshuffle key is a raw uint32[2] — never client-shard
        # it (shape[0] == m is a false positive at m == 2)
        if _leaf_name(path) == "key":
            return P(*([None] * len(shape)))
        if len(shape) >= 1 and shape[0] == m:
            return P(ca, *([None] * (len(shape) - 1)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(leaf, sampler_sds)


def mesh_client_shards(mesh, *, seeds=False, multi_pod=None):
    """Devices ``flat_pspecs`` splits one replicate's ``[m, N]`` client
    rows over on ``mesh`` (1 without a mesh).  Under the S-batched seed
    executor (``seeds``) the rows keep that placement only beneath a
    dedicated ``'seed'`` axis; otherwise the seeds take the client axes
    (``seed_pspecs``) and every replicate's rows sit on one device."""
    if mesh is None:
        return 1
    ax = _axis_sizes(mesh)
    if seeds and "seed" not in ax:
        return 1
    mp = ("pod" in ax) if multi_pod is None else multi_pod
    return math.prod(ax[a] for a in _client_axes(ax, mp))


def seed_axes_for(mesh, *, multi_pod=None):
    """Which mesh axes the leading seed dimension rides on ``mesh``: the
    dedicated ``'seed'`` axis when the mesh has one
    (``launch/mesh.make_seed_mesh``), else the client axes — the PR 4
    placement where seeds displace the per-seed client sharding.  Feed the
    result straight to ``seed_pspecs(..., seed_axes=...)``."""
    ax = _axis_sizes(mesh)
    if "seed" in ax:
        return "seed"
    mp = ("pod" in ax) if multi_pod is None else multi_pod
    return _client_axes(ax, mp)


def seed_pspecs(spec_tree, *, seed_axes=None):
    """Prepend a leading seed axis to every ``PartitionSpec`` in a spec
    tree — the placement story of the S-batched multi-seed executor
    (``engine.make_seeds_chunk_fn``).

    ``spec_tree`` is an inner (single-seed) spec tree, e.g. from
    ``flat_pspecs`` / ``sampler_pspecs``; the returned tree describes the
    same state with ``[S, ...]`` leaves.  ``seed_axes`` is the mesh
    axis (name or tuple of names) the seed dimension shards over — seeds
    are independent replicates, so this is pure data parallelism.  Any
    inner dimension that was using one of those mesh axes is stripped to
    replicated (a mesh axis can appear at most once per spec): when seeds
    ride ``('pod','data')`` the per-seed client axis gives its placement
    up, which is the right trade exactly when S reaches the device count.
    ``seed_axes=None`` replicates the seed axis (small-S simulation tier)
    and leaves inner placements untouched.
    """
    used = set()
    if seed_axes is not None:
        used = set(seed_axes if isinstance(seed_axes, (tuple, list))
                   else (seed_axes,))

    def strip(dim):
        if isinstance(dim, (tuple, list)):
            kept = tuple(a for a in dim if a not in used)
            return kept if kept else None
        return None if dim in used else dim

    def f(p):
        lead = tuple(seed_axes) if isinstance(seed_axes, (tuple, list)) \
            else seed_axes
        return P(lead, *[strip(d) for d in p])

    return jax.tree.map(f, spec_tree, is_leaf=lambda x: isinstance(x, P))


def batch_pspecs(mesh, batches_shape, *, multi_pod=False, mode="tp"):
    """FL round batches [m, s, b, ...] -> client axis sharded; in 'dp' mode
    the within-client batch dim additionally takes the 'model' axis."""
    ax = _axis_sizes(mesh)
    client_axes = _client_axes(ax, multi_pod)
    md = ax.get("model", 0)

    def f(leaf):
        rest = [None] * (len(leaf.shape) - 1)
        if mode == "dp" and len(leaf.shape) >= 3 and _div(leaf.shape[2], md):
            rest[1] = "model"  # [m, s, b, ...] -> b over 'model'
        return P(client_axes, *rest)

    return jax.tree.map(f, batches_shape)


def serve_batch_pspecs(mesh, batch_size):
    """Serving inputs tokens [B,1] / pos [B]."""
    ax = _axis_sizes(mesh)
    b_ax = "data" if _div(batch_size, ax.get("data", 1)) else None
    return P(b_ax, None), P(b_ax)


def cache_pspecs(cfg, mesh, cache_shape, batch_size):
    """Decode caches.

    Batch shards over 'data' when divisible; the cache sequence dim shards
    over 'model' (context-parallel decode: XLA inserts the softmax-stat
    all-reduce). For tiny batches (long_500k: B=1) the sequence dim takes
    both axes instead.
    """
    ax = _axis_sizes(mesh)
    dd, md = ax.get("data", 1), ax.get("model", 1)
    b_data = _div(batch_size, dd)

    def f(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        stacked = _in_stack(path)
        core = shape[1:] if stacked else shape  # drop unit axis
        spec: tuple
        if name in ("k", "v"):  # [B, alloc, K, hd]
            alloc = core[1]
            if b_data:
                seq_ax = "model" if _div(alloc, md) else None
                spec = ("data", seq_ax, None, None)
            else:
                both = _div(alloc, dd * md)
                spec = (None, ("data", "model") if both else
                        ("model" if _div(alloc, md) else None), None, None)
        elif name == "pos":  # [B, alloc]
            alloc = core[1]
            if b_data:
                spec = ("data", "model" if _div(alloc, md) else None)
            else:
                both = _div(alloc, dd * md)
                spec = (None, ("data", "model") if both else
                        ("model" if _div(alloc, md) else None))
        elif name == "state":  # [B, h, p, n]
            spec = ("data" if b_data else None, None, None, None)
        elif name == "conv":  # [B, W-1, convdim]
            spec = ("data" if b_data else None, None, None)
        elif name == "enc_out":  # [B, Le, d]
            spec = ("data" if b_data else None, None, None)
        else:
            spec = tuple([None] * len(core))
        if stacked:
            spec = (None,) + tuple(spec)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(f, cache_shape)
