"""Federated round engine.

One jitted ``round_fn`` executes a full FL round for every client in
lockstep (vmap over the client axis; on the pod tier that axis is sharded
over ('pod','data') and the aggregation lowers to collectives):

  1. local s-step SGD from each client's start model (per-client stale model
     for FedAWE; the broadcast global for stateless baselines),
  2. innovation G_i = x_start − x_end,
  3. strategy aggregation (echo + implicit gossip for FedAWE).

The engine is model-agnostic: it sees only a trainable pytree and a loss
function ``loss_fn(trainable, frozen, batch, rng) -> scalar``.

With ``FLConfig.flat_state`` the persistent state lives on the flat
substrate (core/flatten.py): the global is one contiguous [N] f32 vector,
the client stack one [m, N] buffer, and strategies aggregate through their
fused ``aggregate_flat`` path — pytrees only reappear at the local-SGD entry
and at eval/checkpoint boundaries (``global_trainables``). Stateless
strategies keep no client stack at all; their local SGD starts from a
broadcast *view* of the flat global instead of a materialized copy.

Three executors drive the round function:

  * host loop (``run_rounds`` default): one jitted dispatch per round,
    batches sampled on the host and uploaded, one blocking metrics fetch
    per round.  Simple, and the reference for parity tests.
  * chunked executor (``make_chunk_fn`` / ``run_rounds(chunk_rounds=K)``):
    K rounds execute inside a single jit as a ``jax.lax.scan``, so a chunk
    costs exactly ONE dispatch.  ``donate_argnums`` on ``FLState`` and the
    ``SamplerState`` aliases the dominant ``[m, N]`` client stack (and
    every other state buffer, plus the sampler's ``[m, cap]`` permutation)
    input->output, so rounds update in place; batches are gathered on
    device from a resident ``data.federated.device_store`` by the STATEFUL
    sampler carried in the scan — ``sample_fn(store, sampler_state,
    fold_in(data_key, t)) -> (batches, sampler_state)`` (see
    ``data.federated.make_device_sampler``: ``"uniform"`` i.i.d. draws or
    ``"epoch"`` exactly-once-per-epoch permutation walks).  A host loop
    driven through the same sampler, seeds, and initial sampler state sees
    the identical stream, which is how parity is tested.  Metrics come
    back stacked ``[K]`` and are fetched with a single ``jax.device_get``
    per chunk.  Optional in/out shardings place the ``[m, N]`` stack and
    the sampler buffers over the ``('pod','data')`` mesh axes
    (sharding/rules.flat_pspecs + sampler_pspecs) so the fused flat
    aggregation lowers to the implicit-gossip all-reduce; eval/checkpoint
    align to chunk boundaries.
  * seed-batched executor (``make_seeds_chunk_fn``): the chunk body vmapped
    over a leading seed axis — ONE dispatch advances S independent seed
    replicates K rounds each (states stacked with ``stack_seeds``, per-seed
    data keys, shared store), donated and shardable via
    sharding/rules.seed_pspecs (on a dedicated ``('seed','pod','data')``
    mesh from launch/mesh.make_seed_mesh, or over the client axes).
    Per-seed results are bit-identical to S single-seed chunked runs,
    which is how the paper's multi-seed experiment grid
    (launch/experiments.py) runs as one-dispatch cells.
  * packed grid executor (``make_grid_chunk_fn``): C seed-batched cell
    bodies unrolled inside ONE donated jit — one dispatch advances a whole
    shape-compatible group of grid cells (C cells x S seeds x K rounds),
    the scaling step behind ``launch/experiments.py --packed``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree_util as tu
from repro.core.availability import AvailabilityCfg, probs_at, sample_active
from repro.core.flatten import FlatSpec, resident_dtype
from repro.core.strategies import Strategy, get_strategy


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """Static config of the federated optimization (hashable; closed over
    by the jitted round function — changing any field retraces).

    ``sparse_cohort`` > 0 switches the flat engine to the cohort-centric
    round path (core/cohort.py): the round's active client rows are
    gathered into a ``[c_max, N]`` f32 working set, local SGD and
    aggregation run on the working set, and results scatter back into the
    resident ``[m, N]`` stack — O(cohort) round cost over an O(m) resident
    footprint, with actives beyond the cap deterministically deferred
    (``n_deferred`` metric).  Requires ``flat_state`` and a sampler built
    with ``emit="cols"``.  ``resident_dtype`` stores the resident stacks
    (client stack + model-shaped strategy memory) below accumulation
    precision (``flatten.RESIDENT_DTYPES``; gather promotes to f32,
    scatter demotes) — only meaningful on the sparse path."""
    m: int                      # number of clients
    s: int = 10                 # local steps per round
    eta_l: float = 0.05         # local lr (eta_0; 1/sqrt(t/10+1) schedule)
    eta_g: float = 1.0          # global lr
    strategy: str = "fedawe"
    lr_schedule: bool = True    # paper's eta_l / sqrt(t/10 + 1)
    use_kernel: bool = False    # fused Pallas echo-aggregate
    flat_state: bool = False    # flat [m, N] substrate (core/flatten.py)
    grad_clip: float = 0.5      # paper uses max-norm 0.5
    sparse_cohort: int = 0      # cohort cap c_max (0 = dense rounds)
    resident_dtype: str = "float32"   # [m, N] stack storage dtype

    def __post_init__(self):
        resident_dtype(self.resident_dtype)  # validate the name eagerly
        if self.sparse_cohort:
            assert self.sparse_cohort > 0, self.sparse_cohort
            assert self.flat_state, \
                "sparse_cohort needs the flat [m, N] substrate (flat_state)"
        elif self.resident_dtype != "float32":
            raise ValueError(
                "resident_dtype below f32 needs sparse_cohort > 0: only "
                "the cohort path has the gather-promote / accumulate-"
                "demote boundary (core/cohort.py); the dense engine "
                "reads the stack in place")


class FLState(NamedTuple):
    """Whole persistent state of a run — the (donated) executor carry.

    Every field owns its buffer (``init_fl_state`` copies), because the
    chunked executors donate the entire tuple; ``spec`` is leafless static
    metadata and survives ``jax.tree`` operations unchanged.  Under the
    S-batched executor every array leaf grows a leading ``[S]`` seed axis
    (``stack_seeds``)."""
    global_tr: Any              # global trainables ([N] flat when flat_state)
    clients_tr: Any             # [m, ...] stacked trainables (or None;
                                # [m, N] flat when flat_state)
    tau: jnp.ndarray            # [m] int32, init -1
    t: jnp.ndarray              # scalar int32
    extra: Any                  # strategy state
    markov: jnp.ndarray         # availability markov state [m]
    rng: jnp.ndarray
    spec: Any = None            # FlatSpec (static treedef metadata) or None
    fault: Any = None           # fault-injection carry (core/faults.py):
                                # [T, m] trace / [m] cluster labels, or None
    stale: Any = None           # semi-async carry (core/staleness.py):
                                # [tau_max, m, N] pending-update ring buffer
                                # + [tau_max, m] ages (+ delay trace), or None


def init_fl_state(rng, cfg: FLConfig, trainable_template, *,
                  clients_sharding=None, fault=None, stale=None) -> FLState:
    """``clients_sharding`` (a ``jax.sharding.Sharding``) places every
    ``[m, N]`` buffer — the client stack and model-shaped strategy memory —
    on its final sharding at birth (compiled broadcast straight into the
    sharded layout) instead of materializing replicated and resharding.
    ``fault`` is the fault-injection carry from
    ``faults.init_fault_state`` (a ``[T, m]`` replay trace and/or ``[m]``
    cluster labels, or None) — read-only state that rides the donated
    scan carry like the markov state does.  ``stale`` is the semi-async
    carry from ``staleness.init_staleness_state`` (the ``[tau_max, m, N]``
    pending-update ring buffer + ``[tau_max, m]`` ages, or None) — a
    READ-WRITE carry the round function advances every round."""
    strat = get_strategy(cfg.strategy)
    tau = jnp.full((cfg.m,), -1, jnp.int32)
    markov = jnp.ones((cfg.m,), jnp.float32)
    if cfg.flat_state:
        spec = FlatSpec.from_tree(trainable_template)
        # copy=True: the state must own its buffers — flatten of a 1-leaf
        # f32 tree is a no-op view of the template, and the chunked
        # executor donates (invalidates) every state buffer
        g = jnp.array(spec.flatten(trainable_template), copy=True)
        # sparse cohort residency: the resident stacks (client stack +
        # model-shaped strategy memory) are born in the residency dtype;
        # f32 residency is the identity and keeps the dense build
        # byte-identical.  With a staleness carry the round path runs in
        # dense lanes (the ring buffer is O(m·N) anyway), so the memory
        # strategies keep their dense f32 extra structure there.
        rdt = resident_dtype(cfg.resident_dtype)

        def _init_extra(gg):
            if cfg.sparse_cohort and stale is None and \
                    strat.init_extra_cohort is not None:
                return strat.init_extra_cohort(gg, cfg.m, rdt)
            return strat.init_extra(gg, cfg.m)

        # stateless strategies never materialize the [m, N] client stack
        clients = None
        if strat.stateful_clients:
            clients = jax.jit(
                lambda gg: jnp.broadcast_to(gg.astype(rdt)[None],
                                            (cfg.m, spec.size)),
                out_shardings=clients_sharding)(g)
        if clients_sharding is not None and \
                hasattr(clients_sharding, "mesh"):
            # [m, N] strategy memory (MIFA/FedVARP) is also born on its
            # final sharding — jit the init with per-leaf out_shardings
            # (everything not stack-shaped stays replicated)
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            extra_sds = jax.eval_shape(_init_extra, g)
            out_sh = jax.tree.map(
                lambda sds: clients_sharding
                if tuple(sds.shape) == (cfg.m, spec.size)
                else NamedSharding(clients_sharding.mesh,
                                   P(*([None] * len(sds.shape)))),
                extra_sds)
            extra = jax.jit(_init_extra, out_shardings=out_sh)(g)
        else:
            extra = _init_extra(g)
        return FLState(g, clients, tau, jnp.zeros((), jnp.int32), extra,
                       markov, rng, spec, fault, stale)
    clients = tu.tree_broadcast(trainable_template, cfg.m)
    extra = strat.init_extra(trainable_template, cfg.m)
    return FLState(
        # copy=True: the state owns its buffers (donation-safe) instead of
        # aliasing the caller's template pytree
        global_tr=jax.tree.map(lambda x: jnp.array(x, copy=True),
                               trainable_template),
        clients_tr=clients,
        tau=tau,
        t=jnp.zeros((), jnp.int32),
        extra=extra,
        markov=markov,
        rng=rng,
        fault=fault,
        stale=stale,
    )


def global_trainables(state: FLState):
    """Trainable pytree of the global model — the eval/checkpoint boundary
    where flat state is unflattened back to leaf dtypes."""
    if state.spec is None:
        return state.global_tr
    return state.spec.unflatten(state.global_tr)


def client_trainables(state: FLState):
    """Client-stacked trainable pytree ([m, ...] leaves), or None when the
    strategy keeps no per-client state on the flat substrate."""
    if state.spec is None:
        return state.clients_tr
    if state.clients_tr is None:
        return None
    return state.spec.unflatten_stacked(state.clients_tr)


def _masked_mean(v, w):
    """``sum(v * w) / max(sum(w), 1)`` over the client axis, summed in a
    fixed pairwise order.

    XLA may order a ``reduce`` differently depending on what it fuses it
    with: the dense round fuses the loss sum with local SGD's mean over
    the ``s`` steps, while the cohort round's scatter splits that fusion,
    and the two sums then differ in the last bit.  Elementwise adds are
    never reassociated, so summing by halving keeps every round path's
    metric bit-identical for the same per-client values."""
    x = v * w
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
        x = x[0::2] + x[1::2]
    return x[0] / jnp.maximum(jnp.sum(w), 1.0)


def _clip(g, max_norm):
    if not max_norm:
        return g
    n = tu.tree_norm(g)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-12))
    return tu.tree_scale(scale, g)


def local_sgd(trainable, frozen, batches, rng, *, s, eta_l, loss_fn,
              grad_clip=0.0):
    """s local SGD steps. batches: pytree with leading step axis [s, ...].
    Returns (x_end, mean_loss)."""
    gfn = jax.value_and_grad(loss_fn)

    def step(carry, inp):
        x, key = carry
        mb, _ = inp
        key, sub = jax.random.split(key)
        loss, g = gfn(x, frozen, mb, sub)
        g = _clip(g, grad_clip)
        x = jax.tree.map(
            lambda xx, gg: (xx.astype(jnp.float32)
                            - eta_l * gg.astype(jnp.float32)).astype(xx.dtype),
            x, g)
        return (x, key), loss

    (x_end, _), losses = jax.lax.scan(step, (trainable, rng),
                                      (batches, jnp.arange(s)))
    return x_end, jnp.mean(losses)


def make_round_fn(cfg: FLConfig, loss_fn: Callable, frozen: Any,
                  avail_cfg: AvailabilityCfg, base_p, fault_cfg=None,
                  staleness_cfg=None):
    """Build the jittable round function (frozen params closed over —
    fine when frozen is empty/small; the pod tier uses
    make_round_fn_with_frozen so FSDP-sharded bases stay runtime args).

    loss_fn(trainable, frozen, batch, rng) -> scalar.
    Returned fn: (state, batches[m, s, ...]) -> (state, metrics).
    """
    inner = make_round_fn_with_frozen(cfg, loss_fn, avail_cfg, base_p,
                                      fault_cfg=fault_cfg,
                                      staleness_cfg=staleness_cfg)

    def round_fn(state: FLState, batches):
        return inner(state, frozen, batches)

    return round_fn


def make_round_fn_with_frozen(cfg: FLConfig, loss_fn: Callable,
                              avail_cfg: AvailabilityCfg, base_p,
                              fault_cfg=None, staleness_cfg=None):
    """Variant taking frozen params as a runtime argument:
    (state, frozen, batches) -> (state, metrics).

    ``fault_cfg`` (a ``faults.FaultCfg``) splits the availability mask in
    two: ``mask`` (compute — who runs local SGD; trace replay and cluster
    blackouts apply here) and ``mask_upload`` (who actually delivers —
    the mid-round survival draw plus update sanitization).  Only
    delivering clients contribute to aggregation, update client state /
    τ, or advance participation estimates; the metrics dict grows
    ``n_dropped`` / ``n_rejected`` per round.  ``fault_cfg=None`` is
    byte-identical to the fault-free engine (same rng split count, same
    metrics keys).

    ``staleness_cfg`` (a ``staleness.StalenessCfg``, flat substrate only)
    makes rounds semi-asynchronous: a client available at round ``t``
    computes on the model it holds but its update arrives at ``t + d``
    (``d <= tau_max`` drawn from the configured delay dynamics) through
    the ``FLState.stale`` pending-update ring buffer.  A client with an
    in-flight update is busy — unavailable to compute — until it
    delivers, which bounds every delivery to exactly its drawn delay.
    Arrivals aggregate with discount ``gamma ** d`` and the fault layer
    applies at DELIVERY time (a straggler's update can still drop
    mid-round or fail sanitization when it lands); the metrics dict grows
    ``n_stale`` / ``mean_staleness`` per round.  ``staleness_cfg=None``
    — or ``tau_max = 0``, normalized to None here — is byte-identical to
    the synchronous engine."""
    strat = get_strategy(cfg.strategy)
    if fault_cfg is not None:
        from repro.core import faults as _faults
    if staleness_cfg is not None and staleness_cfg.tau_max == 0:
        # tau_max = 0 IS the synchronous engine: normalize so the build is
        # byte-identical (same rng split count, same metrics keys)
        staleness_cfg = None
    if staleness_cfg is not None:
        assert cfg.flat_state, \
            "staleness_cfg needs the flat [m, N] substrate (flat_state)"
        from repro.core import staleness as _stale
    c_max = min(int(cfg.sparse_cohort), cfg.m) if cfg.sparse_cohort else 0
    if c_max:
        from repro.core import cohort as _cohort
        from repro.data import federated as _fed
        rdt = resident_dtype(cfg.resident_dtype)
        if staleness_cfg is None:
            assert strat.aggregate_cohort is not None, \
                f"strategy {strat.name!r} has no aggregate_cohort path"

    def round_fn(state: FLState, frozen, batches):
        n_keys = 3 + (fault_cfg is not None) + (staleness_cfg is not None)
        keys = jax.random.split(state.rng, n_keys)
        rng, k_av, k_loc = keys[0], keys[1], keys[2]
        k_up = keys[3] if fault_cfg is not None else None
        k_delay = keys[-1] if staleness_cfg is not None else None
        mask, markov = sample_active(k_av, avail_cfg, base_p, state.t,
                                     state.markov)
        probs_t = probs_at(avail_cfg, base_p, state.t)
        if fault_cfg is not None:
            mask = _faults.compute_mask(fault_cfg, state.fault, mask,
                                        state.t)
        if staleness_cfg is not None:
            # arrivals due this round, then busy gating: an in-flight
            # client (including one landing now) does not compute at t
            arrived, arr_age, arr_buf = _stale.drain(state.stale, state.t)
            mask = mask * (1.0 - _stale.busy_mask(state.stale))
            delay = _stale.draw_delay(staleness_cfg, state.stale, k_delay,
                                      state.t, cfg.m)
        if c_max:
            # cohort selection AFTER every availability layer (trace,
            # blackout, busy gating): a slot is never wasted on a client
            # that could not compute anyway.  Actives beyond the cap are
            # deferred BEFORE local work — the effective mask zeroes them,
            # so no computed update is ever silently dropped.
            idx, n_deferred = _cohort.cohort_select(mask, c_max)
            mask_c = jnp.take(mask, idx)
            mask = jnp.zeros_like(mask).at[idx].set(mask_c)

        eta_l = cfg.eta_l
        if cfg.lr_schedule:
            eta_l = cfg.eta_l / jnp.sqrt(state.t.astype(jnp.float32) / 10.0 + 1.0)

        loc_rngs = jax.random.split(k_loc, cfg.m)
        if cfg.flat_state:
            spec = state.spec

            def local(x0_flat, b, k):
                xe, loss = local_sgd(spec.unflatten(x0_flat), frozen, b, k,
                                     s=cfg.s, eta_l=eta_l, loss_fn=loss_fn,
                                     grad_clip=cfg.grad_clip)
                return spec.flatten(xe), loss

            if c_max:
                # cohort-local work at O(c): gather the cohort's data rows
                # and state rows only.  The sampler emitted per-client
                # column draws over the FULL population (emit="cols") and
                # loc_rngs split over the full [m], so every cohort row
                # consumes bitwise the batch columns and rng stream the
                # dense engine would give that client.
                cols, store = batches["cols"], batches["store"]
                q = cols.shape[1]
                b_c = _fed.gather_batches_at(
                    store, jnp.take(cols, idx, axis=0), idx, cfg.s,
                    q // cfg.s)
                if strat.stateful_clients:
                    start_c = _cohort.cohort_gather(state.clients_tr, idx)
                else:
                    start_c = jnp.broadcast_to(state.global_tr[None],
                                               (c_max, spec.size))
                x_end_c, losses_c = jax.vmap(local)(
                    start_c, b_c, jnp.take(loc_rngs, idx, axis=0))
                G_c = start_c - x_end_c
            if c_max and staleness_cfg is None:
                # pure cohort round: aggregation, client/tau updates and
                # the resident scatter all run at O(c·N)
                tau_c = jnp.take(state.tau, idx)
                mask_upload_c = None
                if fault_cfg is not None:
                    mask_upload_c, n_dropped, n_rejected = \
                        _faults.upload_mask_cohort(fault_cfg, k_up, cfg.m,
                                                   idx, mask_c, G_c)
                    if fault_cfg.sanitize:
                        keep = mask_upload_c[:, None] > 0
                        x_end_c = jnp.where(keep, x_end_c, start_c)
                        G_c = jnp.where(keep, G_c, 0.0)
                mu_c = mask_c if mask_upload_c is None else mask_upload_c
                mu_full = jnp.zeros((cfg.m,),
                                    jnp.float32).at[idx].set(mu_c)
                probs_c = jnp.take(probs_t, idx) \
                    if getattr(probs_t, "ndim", 0) else probs_t
                new_global, rows, write, new_extra = strat.aggregate_cohort(
                    global_flat=state.global_tr, cohort_flat=start_c,
                    x_end=x_end_c, G=G_c, mask=mask_c, t=state.t,
                    tau_c=tau_c, probs_c=probs_c, extra=state.extra,
                    eta_g=cfg.eta_g, m_total=cfg.m, idx=idx,
                    mu_full=mu_full, use_kernel=cfg.use_kernel,
                    mask_upload=mask_upload_c)
                new_tau = jnp.where(mu_full > 0, state.t, state.tau)
                new_clients = state.clients_tr
                if rows is not None and new_clients is not None:
                    new_clients = _cohort.cohort_scatter(
                        state.clients_tr, idx, rows, write)
                # full-[m] metric inputs (O(m) vectors, not O(m·N)) so the
                # shared metrics blocks below apply unchanged: scattered
                # lanes carry exact zeros wherever the mask does
                losses = jnp.zeros((cfg.m,),
                                   jnp.float32).at[idx].set(losses_c)
                mask_upload = None if mask_upload_c is None else mu_full
            else:
                if c_max:
                    # sparse + staleness: the pending-update ring buffer
                    # is O(m·N) per round regardless, so cohort results
                    # scatter into dense lanes and the delivery / fault /
                    # aggregation code below runs unchanged — non-cohort
                    # lanes carry zero weight and G = 0 exactly
                    if strat.stateful_clients:
                        start = state.clients_tr.astype(jnp.float32)
                    else:
                        start = jnp.broadcast_to(state.global_tr[None],
                                                 (cfg.m, spec.size))
                    x_end = start.at[idx].set(x_end_c)
                    losses = jnp.zeros((cfg.m,),
                                       jnp.float32).at[idx].set(losses_c)
                else:
                    # stateless: a broadcast VIEW of the flat global,
                    # never a copy
                    start = state.clients_tr if strat.stateful_clients \
                        else jnp.broadcast_to(state.global_tr[None],
                                              (cfg.m, spec.size))
                    x_end, losses = jax.vmap(local)(start, batches,
                                                    loc_rngs)
                G = start - x_end
                if staleness_cfg is not None:
                    # delivery candidates: synchronous computes (drawn
                    # d = 0) plus ring-buffer arrivals — disjoint sets,
                    # since an arriving client was busy and did not
                    # compute this round
                    now = mask * (delay == 0).astype(jnp.float32)
                    defer = mask * (delay > 0).astype(jnp.float32)
                    deliver = now + arrived
                    G_eff = jnp.where(arrived[:, None] > 0, arr_buf,
                                      jnp.where(now[:, None] > 0, G, 0.0))
                    x_end_eff = jnp.where(arrived[:, None] > 0,
                                          start - arr_buf, x_end)
                    age_eff = jnp.where(arrived > 0, arr_age, 0.0)
                else:
                    deliver, G_eff, x_end_eff = mask, G, x_end
                mask_upload = None
                if fault_cfg is not None:
                    # under staleness the fault layer acts at DELIVERY
                    # time: a stale arrival can still drop mid-round or
                    # fail sanitization when it lands
                    mask_upload, n_dropped, n_rejected = \
                        _faults.upload_mask(fault_cfg, k_up, deliver,
                                            G_eff)
                    if fault_cfg.sanitize:
                        # scrub demoted rows: a 0-weighted NaN still
                        # poisons a w·G reduction (0 * NaN = NaN), so
                        # rejected clients' rows must hold finite values,
                        # not just zero weight
                        keep = mask_upload[:, None] > 0
                        x_end_eff = jnp.where(keep, x_end_eff, start)
                        G_eff = jnp.where(keep, G_eff, 0.0)
                if staleness_cfg is not None:
                    mu0 = deliver if mask_upload is None else mask_upload
                    w_disc = mu0 if staleness_cfg.gamma >= 1.0 else \
                        mu0 * jnp.power(jnp.float32(staleness_cfg.gamma),
                                        age_eff)
                    agg_mask, agg_kwargs = mu0, dict(mask_upload=w_disc,
                                                     ages=age_eff)
                else:
                    agg_mask, agg_kwargs = mask, dict(
                        mask_upload=mask_upload)
                new_global, new_clients, new_tau, new_extra = \
                    strat.aggregate_flat(
                        global_flat=state.global_tr, clients_flat=start,
                        x_end=x_end_eff, G=G_eff, mask=agg_mask,
                        t=state.t, tau=state.tau, probs=probs_t,
                        extra=state.extra, eta_g=cfg.eta_g,
                        use_kernel=cfg.use_kernel, **agg_kwargs)
                if staleness_cfg is not None:
                    # raw (unsanitized, undiscounted) innovations enter
                    # the ring; faults and the gamma discount apply at
                    # delivery
                    new_stale = _stale.step_buffer(state.stale, state.t,
                                                   defer, delay, G)
                if c_max and new_clients is not None:
                    # demote the full stack back to residency (identity
                    # for f32); the dense-lane aggregate ran in f32
                    new_clients = new_clients.astype(rdt)
        else:
            start = state.clients_tr if strat.stateful_clients else \
                tu.tree_broadcast(state.global_tr, cfg.m)

            x_end, losses = jax.vmap(
                lambda x0, b, k: local_sgd(x0, frozen, b, k, s=cfg.s,
                                           eta_l=eta_l, loss_fn=loss_fn,
                                           grad_clip=cfg.grad_clip)
            )(start, batches, loc_rngs)
            G = tu.tree_sub(start, x_end)

            mask_upload = None
            if fault_cfg is not None:
                mask_upload, n_dropped, n_rejected = _faults.upload_mask(
                    fault_cfg, k_up, mask, G)
                if fault_cfg.sanitize:
                    keep = mask_upload > 0
                    x_end = jax.tree.map(
                        lambda xe, st_: jnp.where(
                            tu._bshape(keep, xe), xe, st_), x_end, start)
                    G = jax.tree.map(
                        lambda g: jnp.where(tu._bshape(keep, g), g,
                                            jnp.zeros_like(g)), G)
            new_global, new_clients, new_tau, new_extra = strat.aggregate(
                global_tr=state.global_tr, clients_tr=start, G=G, mask=mask,
                t=state.t, tau=state.tau, probs=probs_t, extra=state.extra,
                eta_g=cfg.eta_g, use_kernel=cfg.use_kernel, x_end=x_end,
                mask_upload=mask_upload)

        if staleness_cfg is not None:
            # loss/n_active describe who COMPUTED this round; the delivery
            # side (mean_echo over delivered, n_stale arrivals due,
            # mean_staleness of what aggregated) gets its own keys
            den_mu = jnp.maximum(jnp.sum(mu0), 1.0)
            safe = losses if fault_cfg is None else \
                jnp.where(jnp.isfinite(losses), losses, 0.0)
            metrics = dict(
                loss=_masked_mean(safe, mask),
                n_active=jnp.sum(mask),
                mean_echo=jnp.sum(
                    (state.t - state.tau).astype(jnp.float32) * mu0)
                / den_mu,
                n_stale=jnp.sum(arrived),
                mean_staleness=jnp.sum(age_eff * mu0) / den_mu,
            )
            if fault_cfg is not None:
                metrics.update(n_dropped=n_dropped, n_rejected=n_rejected)
        elif fault_cfg is None:
            metrics = dict(
                loss=_masked_mean(losses, mask),
                n_active=jnp.sum(mask),
                mean_echo=jnp.sum(
                    (state.t - state.tau).astype(jnp.float32) * mask)
                / jnp.maximum(jnp.sum(mask), 1.0),
            )
        else:
            # delivered clients define the observed metrics; a rejected
            # client's loss may itself be non-finite, so it is excluded
            # by value, not just by weight
            mu = mask_upload
            safe = jnp.where(jnp.isfinite(losses), losses, 0.0)
            metrics = dict(
                loss=_masked_mean(safe, mu),
                n_active=jnp.sum(mask),
                mean_echo=jnp.sum(
                    (state.t - state.tau).astype(jnp.float32) * mu)
                / jnp.maximum(jnp.sum(mu), 1.0),
                n_dropped=n_dropped,
                n_rejected=n_rejected,
            )
        if c_max:
            metrics["n_deferred"] = n_deferred
        new_state = state._replace(
            global_tr=new_global, clients_tr=new_clients, tau=new_tau,
            t=state.t + 1, extra=new_extra, markov=markov, rng=rng)
        if staleness_cfg is not None:
            new_state = new_state._replace(stale=new_stale)
        return new_state, metrics

    return round_fn


def make_chunk_fn(cfg, round_fn, sample_fn, chunk_rounds, *,
                  with_frozen=False, donate=True, jit=True,
                  in_shardings=None, out_shardings=None):
    """Chunked round executor: K = ``chunk_rounds`` rounds per dispatch.

    Wraps ``round_fn`` in a ``jax.lax.scan`` inside a single jit with
    ``donate_argnums`` on the ``FLState`` and ``SamplerState`` arguments,
    so the dominant ``[m, N]`` client stack (and the global, tau, strategy
    memory, the sampler's ``[m, cap]`` permutation buffer, ...) is updated
    in place and a chunk costs exactly one dispatch.  The scan carry is
    ``(FLState, SamplerState)``: per round, batches come from the stateful
    sampler ``sample_fn(store, sampler_state, fold_in(data_key, state.t))
    -> (batches, sampler_state)`` (see ``data.federated.
    make_device_sampler``) — keyed by the *global* round counter and the
    carried sampler state, so a host loop driven through the same sampler,
    seeds, and initial sampler state sees identical data.  Metrics come
    back stacked ``[K]`` per key.

    Returned callable: ``chunk(state, sampler_state, store, data_key)`` —
    or ``chunk(state, frozen, sampler_state, store, data_key)`` with
    ``with_frozen`` (pod tier, FSDP-sharded bases stay runtime args) —
    returning ``(state, sampler_state, metrics)``.

    ``cfg`` is the ``FLConfig`` the round function was built from (kept for
    signature symmetry with ``make_round_fn``; the executor itself is
    config-agnostic).  ``in_shardings``/``out_shardings`` thread
    ``NamedSharding`` pytrees through the jit so the flat ``[m, N]`` stack
    and the sampler's ``[m]``/``[m, cap]`` buffers stay on their
    ``('pod','data')`` placement and the fused aggregation lowers to the
    implicit-gossip all-reduce (sharding/rules.flat_pspecs +
    sharding/rules.sampler_pspecs).
    """
    del cfg
    K = int(chunk_rounds)
    assert K >= 1, "chunk_rounds must be >= 1"

    def _scan(state, frozen, sampler_state, store, data_key):
        def body(carry, _):
            st, ss = carry
            batches, ss = sample_fn(store, ss,
                                    jax.random.fold_in(data_key, st.t))
            if with_frozen:
                st, metrics = round_fn(st, frozen, batches)
            else:
                st, metrics = round_fn(st, batches)
            return (st, ss), metrics

        (state, sampler_state), metrics = jax.lax.scan(
            body, (state, sampler_state), None, length=K)
        return state, sampler_state, metrics

    if with_frozen:
        def chunk(state, frozen, sampler_state, store, data_key):
            return _scan(state, frozen, sampler_state, store, data_key)
        donate_idx = (0, 2)
    else:
        def chunk(state, sampler_state, store, data_key):
            return _scan(state, None, sampler_state, store, data_key)
        donate_idx = (0, 1)

    if not jit:
        return chunk
    kwargs = {}
    if donate:
        kwargs["donate_argnums"] = donate_idx
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    return jax.jit(chunk, **kwargs)


def stack_seeds(trees):
    """Stack a list of identically-structured pytrees along a new leading
    seed axis: ``[tree_0, ..., tree_{S-1}] -> tree with [S, ...] leaves``.

    This is how per-seed replicate state enters the S-batched executor
    (``make_seeds_chunk_fn``): build each seed's ``FLState`` /
    ``SamplerState`` / data key exactly as a single-seed run would, then
    stack.  ``jnp.stack`` is bitwise-preserving, so slice ``j`` of the
    stacked tree is the byte-for-byte input of independent run ``j`` —
    the root of the multi-seed parity guarantee.  Static treedef metadata
    (the ``FlatSpec`` riding in ``FLState.spec``) is leafless and passes
    through unchanged; all trees must share it.
    """
    assert trees, "stack_seeds needs at least one tree"
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def index_seed(tree, j):
    """Slice seed replicate ``j`` out of a seed-stacked pytree (inverse of
    one row of ``stack_seeds``): ``[S, ...]`` leaves -> ``[...]`` leaves.
    Used at eval/checkpoint boundaries, where per-seed models are examined
    one at a time (``global_trainables(index_seed(states, j))``)."""
    return jax.tree.map(lambda x: x[j], tree)


def make_seeds_chunk_fn(cfg, round_fn, sample_fn, chunk_rounds, n_seeds, *,
                        with_frozen=False, donate=True, jit=True,
                        in_shardings=None, out_shardings=None):
    """S-batched chunk executor: one dispatch advances ``n_seeds``
    INDEPENDENT seed replicates by ``chunk_rounds`` rounds each.

    This is ``make_chunk_fn``'s scan body vmapped over a leading seed axis:
    the ``FLState``, the ``SamplerState`` and the per-seed data keys carry
    ``[S, ...]`` leaves (built with ``stack_seeds``), while the device
    ``store`` and (with ``with_frozen``) the frozen params are closed over
    and shared by every replicate.  Each replicate evolves exactly as its
    single-seed chunked run would — same availability draws (per-seed
    ``FLState.rng`` / markov state), same sampler stream (per-seed data
    key + carried sampler state) — so per-seed results are bit-identical
    to S independent runs with the corresponding keys; only the dispatch
    is fused.  This scales the *experiment* axis the way the chunked
    executor scales the round axis: an S-seed, K-round cell of the paper's
    grid costs one dispatch instead of S*K.

    Returned callable::

        chunk(states, sampler_states, store, data_keys)
            -> (states, sampler_states, metrics)     # metrics [S, K] per key

    or with ``with_frozen`` (frozen params as runtime arg, pod tier)::

        chunk(states, frozen, sampler_states, store, data_keys)

    ``states``/``sampler_states`` are donated (every per-seed buffer —
    dominated by the ``[S, m, N]`` client stacks — updates in place).
    ``in_shardings``/``out_shardings`` place the seed axis on the mesh
    (``sharding/rules.seed_pspecs``: seeds ride ``('pod','data')`` — or a
    dedicated mesh axis — with any inner client-axis placement they
    displace stripped to replicated).
    """
    del cfg  # kept for signature symmetry with make_chunk_fn
    S = int(n_seeds)
    assert S >= 1, "n_seeds must be >= 1"
    base = make_chunk_fn(None, round_fn, sample_fn, chunk_rounds,
                         with_frozen=with_frozen, donate=False, jit=False)

    if with_frozen:
        def chunk(states, frozen, sampler_states, store, data_keys):
            # frozen/store close over the vmapped fn -> broadcast, unbatched
            return jax.vmap(
                lambda st, ss, dk: base(st, frozen, ss, store, dk)
            )(states, sampler_states, data_keys)
        donate_idx = (0, 2)
    else:
        def chunk(states, sampler_states, store, data_keys):
            return jax.vmap(
                lambda st, ss, dk: base(st, ss, store, dk)
            )(states, sampler_states, data_keys)
        donate_idx = (0, 1)

    if not jit:
        return chunk
    kwargs = {}
    if donate:
        kwargs["donate_argnums"] = donate_idx
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    return jax.jit(chunk, **kwargs)


def make_grid_chunk_fn(cells, chunk_rounds, n_seeds, *, donate=True,
                       jit=True, in_shardings=None, out_shardings=None):
    """Packed grid executor: ONE donated dispatch advances C grid cells x
    ``n_seeds`` seed replicates x ``chunk_rounds`` rounds.

    ``cells`` is a list of ``(round_fn, sample_fn)`` pairs — one per grid
    cell (strategy x availability x sampling knobs are baked into each
    cell's round/sample functions).  Different cells trace different
    computations (static strategy/availability branches), so they cannot
    share one vmap the way seeds do; instead each cell's S-batched chunk
    body (``make_seeds_chunk_fn``) is unrolled INSIDE a single jit.  The
    cells are independent subgraphs, so XLA schedules them concurrently
    and the whole group costs one dispatch per chunk — the grid-packing
    layer (``launch/experiments.run_packed_grid``) bucket-pads near-miss
    cells, merges groups per (S, K, T) and drives one of these per group,
    so a Section 7 grid completes in one or two dispatch streams instead
    of one per cell.  Per-cell, per-seed results stay bit-identical to
    the unpacked ``make_seeds_chunk_fn`` runs (each cell's subgraph is
    the same expression; packing changes scheduling, not math).

    ``in_shardings``/``out_shardings`` compose the packed jit with a live
    seed mesh: ``launch/experiments.grid_chunk_shardings`` zips the
    per-cell ``seed_chunk_shardings`` trees into this function's C-tuple
    argument structure, so every cell keeps the exact placement its
    unpacked executor would use — and the SAME builder must be reused
    for any ``T % K`` tail, or the tail dispatch silently reverts to
    default placement.

    Returned callable::

        packed(states_t, sampler_states_t, stores_t, data_keys_t)
            -> (states_t, sampler_states_t, metrics_t)

    where every argument/result is a C-tuple over cells and element ``i``
    has the ``[S, ...]`` layout of ``make_seeds_chunk_fn`` (stores may
    differ in shape across cells — per-cell Dirichlet partitions).  The
    state and sampler tuples are donated whole.
    """
    assert cells, "make_grid_chunk_fn needs at least one cell"
    bodies = [make_seeds_chunk_fn(None, rf, sf, chunk_rounds, n_seeds,
                                  donate=False, jit=False)
              for rf, sf in cells]

    def packed(states_t, sampler_states_t, stores_t, data_keys_t):
        outs = [body(st, ss, store, dk)
                for body, st, ss, store, dk in zip(
                    bodies, states_t, sampler_states_t, stores_t,
                    data_keys_t)]
        return (tuple(o[0] for o in outs), tuple(o[1] for o in outs),
                tuple(o[2] for o in outs))

    if not jit:
        return packed
    kwargs = {}
    if donate:
        kwargs["donate_argnums"] = (0, 1)
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    return jax.jit(packed, **kwargs)


def run_rounds(state: FLState, round_fn, batch_fn, T, *, jit=True,
               log_every=0, eval_fn=None, eval_every=0,
               chunk_rounds=0, sample_fn=None, store=None, data_key=None,
               sampler_state=None, chunk_fn=None, make_tail_fn=None,
               donate=True, ckpt_fn=None, ckpt_every=0):
    """Run T rounds; returns (state, history list of metric dicts).

    Host loop (default): one dispatch per round, ``batch_fn(t)`` batches,
    and the whole metrics dict fetched with a single ``jax.device_get``
    per round.  When ``batch_fn`` is None and a stateful device sampler is
    given (``sample_fn``/``store``/``data_key``/``sampler_state``), the
    loop threads the ``SamplerState`` through
    ``sample_fn(store, sampler_state, fold_in(data_key, t))`` — the same
    stream the chunked executor's scan carry sees, so epoch-permutation
    sampling behaves identically in both executors.

    Chunked (``chunk_rounds=K > 0``): ``ceil(T / K)`` dispatches through
    ``make_chunk_fn`` (a shorter final chunk covers ``T % K``), with
    device-side sampling via ``sample_fn``/``store``/``data_key``/
    ``sampler_state`` and one metrics fetch per chunk.  ``eval_fn``/
    ``ckpt_fn`` fire at the first chunk boundary at or past each
    ``eval_every``/``ckpt_every`` multiple.  A 2-arg ``ckpt_fn(state,
    t)`` writes eval/export checkpoints; a 3-arg ``ckpt_fn(state, t,
    sampler_state)`` additionally receives the CARRIED sampler state —
    required for a RESUMABLE checkpoint (``checkpointing.save_run_state``),
    since the donated carry is otherwise consumed by the next dispatch
    and never returned.  A prebuilt ``chunk_fn`` (e.g.
    with explicit shardings) is used for full-K chunks when given; because
    an implicitly rebuilt ``T % K`` tail would silently drop those
    shardings, a prebuilt ``chunk_fn`` with ``T % K != 0`` requires
    ``make_tail_fn`` (``make_tail_fn(k) -> executor`` built with the
    caller's shardings) and raises otherwise.
    """
    if chunk_rounds:
        return _run_rounds_chunked(
            state, round_fn, T, chunk_rounds, sample_fn=sample_fn,
            store=store, data_key=data_key, sampler_state=sampler_state,
            chunk_fn=chunk_fn, make_tail_fn=make_tail_fn, jit=jit,
            donate=donate, log_every=log_every, eval_fn=eval_fn,
            eval_every=eval_every, ckpt_fn=ckpt_fn, ckpt_every=ckpt_every)

    _ss = None
    if batch_fn is None:
        assert sample_fn is not None and store is not None \
            and data_key is not None and sampler_state is not None, (
                "host loop needs batch_fn, or a stateful device sampler "
                "(sample_fn + store + data_key + sampler_state)")
        sf = jax.jit(sample_fn) if jit else sample_fn
        _ss = [sampler_state]
        # key by the GLOBAL round counter, like the chunk executor's
        # fold_in(data_key, st.t) — a resumed state (t0 != 0) must not
        # replay the stream from round 0
        t0 = int(state.t)

        def batch_fn(t):
            batches, _ss[0] = sf(store, _ss[0],
                                 jax.random.fold_in(data_key, t0 + t))
            return batches

    f = jax.jit(round_fn) if jit else round_fn
    history = []
    for t in range(T):
        batches = batch_fn(t)
        state, metrics = f(state, batches)
        # one host sync for the whole dict (not one float(v) per key)
        rec = {k: float(v) for k, v in jax.device_get(metrics).items()}
        rec["t"] = t
        if eval_fn is not None and eval_every and (t + 1) % eval_every == 0:
            rec.update(eval_fn(state))
        history.append(rec)
        if ckpt_fn is not None and ckpt_every and (t + 1) % ckpt_every == 0:
            _call_ckpt(ckpt_fn, state, t + 1,
                       _ss[0] if _ss is not None else None)
        if log_every and (t + 1) % log_every == 0:
            print(f"[round {t+1:5d}] " +
                  " ".join(f"{k}={v:.4f}" for k, v in rec.items()
                           if k != "t"))
    return state, history


def _crossed(done, k, every):
    """Did [done-k, done] cross a multiple of ``every``?"""
    return every and (done // every) > ((done - k) // every)


def _call_ckpt(ckpt_fn, state, done, sampler_state):
    """Dispatch a checkpoint hook by arity: 2-arg ``(state, t)`` hooks
    write eval/export checkpoints (the train-CLI default), 3-arg hooks
    also get the carried sampler state so they can write a RESUMABLE
    checkpoint (``checkpointing.save_run_state``) — the executors donate
    the carry, so the hook is the only place both halves are in hand.
    Variadic hooks (``*args``) count as 3-arg: a hook that absorbs
    arguments must get the full run state, never a silent downgrade."""
    import inspect

    try:
        params = inspect.signature(ckpt_fn).parameters.values()
        variadic = any(p.kind == inspect.Parameter.VAR_POSITIONAL
                       for p in params)
        n = 3 if variadic else len(params)
    except (TypeError, ValueError):  # builtins/partials without signature
        n = 2
    if n >= 3:
        ckpt_fn(state, done, sampler_state)
    else:
        ckpt_fn(state, done)


def _run_rounds_chunked(state, round_fn, T, K, *, sample_fn, store, data_key,
                        sampler_state, chunk_fn, make_tail_fn, jit, donate,
                        log_every, eval_fn, eval_every, ckpt_fn, ckpt_every):
    assert data_key is not None, "chunked executor needs a data PRNG key"
    assert sampler_state is not None, (
        "chunked executor needs the carried sampler_state "
        "(init_sampler_state(store, data_key) from make_device_sampler)")
    if chunk_fn is not None and T % K and make_tail_fn is None:
        # rebuilding the T % K tail here from round_fn would silently drop
        # the caller's shardings (the prebuilt chunk_fn may place the
        # [m, N] stack on the production mesh) — demand an explicit tail
        # builder instead of degrading the placement
        raise ValueError(
            f"prebuilt chunk_fn with T={T} not a multiple of "
            f"chunk_rounds={K}: an implicitly built tail executor would "
            "not carry the chunk_fn's shardings; pass make_tail_fn(k) "
            "built with the same shardings, or make T a multiple of K")
    if chunk_fn is None:
        assert sample_fn is not None, (
            "chunked executor needs sample_fn to build the chunk "
            "executor and any T % chunk_rounds tail")
        chunk_fn = make_chunk_fn(None, round_fn, sample_fn, K,
                                 donate=donate, jit=jit)
    tail_fn = None
    history, done = [], 0
    warmed = set()
    while done < T:
        k = min(K, T - done)
        if k == K:
            f = chunk_fn
        else:
            if tail_fn is None:
                tail_fn = (make_tail_fn(k) if make_tail_fn is not None
                           else make_chunk_fn(None, round_fn, sample_fn, k,
                                              donate=donate, jit=jit))
            f = tail_fn
        if id(f) in warmed:
            # steady-state dispatch is transfer-free by construction
            # (state, sampler carry, store and key are all device
            # resident); the guard turns any regression — a numpy batch
            # or host scalar sneaking into the chunk call — into a hard
            # error instead of a silent per-chunk upload.  The first
            # call per executable stays unguarded: compilation commits
            # baked constants to device, an intentional one-time upload.
            with jax.transfer_guard("disallow"):
                state, sampler_state, metrics = f(state, sampler_state,
                                                  store, data_key)
        else:
            state, sampler_state, metrics = f(state, sampler_state, store,
                                              data_key)
            warmed.add(id(f))
        metrics = jax.device_get(metrics)  # ONE host sync per chunk
        for j in range(k):
            rec = {key: float(v[j]) for key, v in metrics.items()}
            rec["t"] = done + j
            history.append(rec)
        done += k
        if eval_fn is not None and _crossed(done, k, eval_every):
            history[-1].update(eval_fn(state))
        if ckpt_fn is not None and _crossed(done, k, ckpt_every):
            _call_ckpt(ckpt_fn, state, done, sampler_state)
        if _crossed(done, k, log_every):
            rec = history[-1]
            print(f"[round {done:5d}] " +
                  " ".join(f"{key}={v:.4f}" for key, v in rec.items()
                           if key != "t"))
    return state, history
