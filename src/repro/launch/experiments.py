"""Scenario-matrix runner for the paper's experiment grid.

The paper's headline claims (FedAWE's linear speedup, robustness across
heterogeneous and non-stationary availability) are claims about a GRID —
strategy x availability dynamics x sampler x heterogeneity — evaluated over
multiple seeds, not about a single run.  This module makes every cell of
that grid a one-command, one-dispatch-per-chunk answer:

  * a **scenario registry**: named cells (``"fedawe/sine"``,
    ``"fedau/markov"``, ...) binding a strategy to an availability process,
    a sampling mode and the Dirichlet heterogeneity knob, with the paper's
    Section 7 grid and the F3AST-style Markov setting (Ribero et al.)
    pre-registered, plus named sub-grids (``GRIDS``) for the paper's
    figures;
  * a **vmapped multi-seed executor**: ``engine.make_seeds_chunk_fn``
    batches the ``FLState``, the ``SamplerState`` and the per-seed data
    keys over a leading seed axis, so ONE jitted dispatch advances S
    independent replicates K rounds (donated in place; the live jit
    carries ``sharding/rules.seed_pspecs`` shardings on a
    ``('seed','pod','data')`` mesh from ``launch/mesh.make_seed_mesh``
    when one is given).  Seed replicate ``j`` is bit-identical to an
    independent single-seed chunked run driven by ``fold_in(rng, j)`` /
    ``fold_in(data_key, j)`` — the parity tests pin this down
    byte-for-byte.  Replication is **shared-template** by default (one
    model init, seeds vary the stochastic draws) or **full**
    (``--replicate full``: per-seed model re-init keyed
    ``fold_in(model_rng, j)``, the paper's fully independent replicates);
  * a **grid-packing layer** (``--packed``): cells group into donated
    dispatch streams (``engine.make_grid_chunk_fn``) — near-miss shapes
    are bucket-padded bit-exactly (sampler-cap columns; see
    ``pack_cells``) and the groups merge to ONE stream per (S, K, T), so
    a whole Section 7 grid advances as C-cells x S-seeds x K-rounds
    dispatches in a single stream.  Composes with ``--seed-mesh``: the
    per-cell shardings zip into the packed jit's C-tuple signature
    (``grid_chunk_shardings``), bit-identical to the unpacked mesh runs;
  * a **reporting layer**: per-seed histories aggregate into mean±std
    curves and a paper-style results table under ``results/``
    (``launch/analysis.aggregate_seed_histories`` / ``seed_summary`` /
    ``write_results_table``).

CLI::

    python -m repro.launch.experiments --list
    python -m repro.launch.experiments --scenario fedawe/sine --seeds 4 \
        --rounds 24 --chunk-rounds 8
    python -m repro.launch.experiments --scenario 'fedawe/*' --seeds 4
    python -m repro.launch.experiments --grid speedup-sine --seeds 8 \
        --packed
"""
from __future__ import annotations

import argparse
import dataclasses
import fnmatch
import json
import os
import re

import jax
import jax.numpy as jnp

from repro.core import (FLConfig, index_seed, init_fl_state,
                        make_grid_chunk_fn, make_round_fn,
                        make_seeds_chunk_fn, stack_seeds)
from repro.core.availability import KINDS, AvailabilityCfg
from repro.core.engine import _crossed, _dispatch
from repro.core.strategies import REGISTRY
from repro.data import (SAMPLING_MODES, init_seed_sampler_states,
                        make_device_sampler, seed_data_keys)
from repro.launch import analysis
from repro.sharding import mesh_client_shards


# ---------------------------------------------------------------------------
# scenario registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Scenario:
    """One named cell of the experiment grid.

    A scenario fixes everything that defines a *comparison point* in the
    paper — the aggregation strategy, the availability process and its
    knobs, the sampling mode, and the Dirichlet heterogeneity ``alpha`` —
    while run-scale knobs (clients, rounds, seeds, batch) stay CLI
    arguments so the same cell runs as a smoke test or a full
    reproduction.  ``availability()`` materializes the ``AvailabilityCfg``
    the round engine consumes.
    """
    name: str
    strategy: str = "fedawe"
    kind: str = "stationary"        # availability dynamics (one of KINDS)
    sampling: str = "uniform"       # device-sampler mode
    alpha: float = 0.1              # Dirichlet heterogeneity (data + avail)
    gamma: float = 0.3              # sine family amplitude
    period: int = 20                # staircase / sine period
    staircase_low: float = 0.4
    cutoff: float = 0.1             # interleaved_sine hard cutoff
    delta_floor: float = 0.0        # Assumption-1 clamp
    markov_up: float = 0.2          # Gilbert-Elliott P(off -> on) scale
    markov_down: float = 0.2        # Gilbert-Elliott P(on -> off)
    eta_l: float = 0.05
    eta_g: float = 1.0
    flat_state: bool = True         # flat [m, N] substrate by default
    # fault-injection knobs (core/faults.py) — all off by default
    upload_survival: float = 1.0    # < 1 enables mid-round dropout
    sanitize: bool = False          # demote non-finite updates to dropped
    norm_cap: float = 0.0           # with sanitize: reject ||G_i|| > cap
    fault_trace: str = ""           # "" or "diurnal": [T, m] replay trace
    blackout_start: int = 0
    blackout_len: int = 0           # > 0: blackout B consecutive rounds
    blackout_every: int = 0         # recurrence period (0 = one-shot)
    blackout_cluster: int = 0       # targeted data cluster (dominant label)
    nu_corr: bool = False           # base_p := adversarial_probs_from_nu
    # semi-async knobs (core/staleness.py) — all off by default
    stale_max: int = 0              # tau_max delay bound (0 = synchronous)
    stale_kind: str = "det"         # delay dynamics: det | geom | trace
    stale_delay: int = 1            # det: every straggler takes this long
    stale_p: float = 0.5            # geom: per-round arrival probability
    stale_gamma: float = 1.0        # delivery discount base (gamma ** d)
    note: str = ""

    def __post_init__(self):
        assert self.strategy in REGISTRY, self.strategy
        assert self.kind in KINDS, self.kind
        assert self.sampling in SAMPLING_MODES, self.sampling
        assert self.fault_trace in ("", "diurnal"), self.fault_trace
        assert self.stale_kind in ("det", "geom", "trace"), self.stale_kind

    def availability(self) -> AvailabilityCfg:
        return AvailabilityCfg(
            kind=self.kind, gamma=self.gamma, period=self.period,
            staircase_low=self.staircase_low, cutoff=self.cutoff,
            delta_floor=self.delta_floor, markov_up=self.markov_up,
            markov_down=self.markov_down)

    def fault(self):
        """The cell's ``FaultCfg``, or None when every fault knob is at
        its fault-free default (so the engine compiles the byte-identical
        no-fault round function)."""
        from repro.core.faults import FaultCfg
        if (self.upload_survival >= 1.0 and not self.sanitize
                and not self.fault_trace and self.blackout_len == 0):
            return None
        return FaultCfg(
            upload_survival=self.upload_survival,
            trace=bool(self.fault_trace),
            blackout_start=self.blackout_start,
            blackout_len=self.blackout_len,
            blackout_every=self.blackout_every,
            blackout_cluster=self.blackout_cluster,
            sanitize=self.sanitize, norm_cap=self.norm_cap)

    def staleness(self):
        """The cell's ``StalenessCfg``, or None when ``stale_max == 0``
        (so the engine compiles the byte-identical synchronous round
        function)."""
        from repro.core.staleness import StalenessCfg
        if self.stale_max == 0:
            return None
        return StalenessCfg(
            tau_max=self.stale_max, kind=self.stale_kind,
            delay=self.stale_delay, p_next=self.stale_p,
            gamma=self.stale_gamma)


SCENARIOS: dict = {}

#: Named sub-grids: lists of scenario names matching the paper's figures.
GRIDS: dict = {}


def register_scenario(sc: Scenario) -> Scenario:
    assert sc.name not in SCENARIOS, f"duplicate scenario {sc.name!r}"
    SCENARIOS[sc.name] = sc
    return sc


def get_scenario(name: str) -> Scenario:
    if name not in SCENARIOS:
        raise KeyError(f"unknown scenario {name!r}; see --list "
                       f"({len(SCENARIOS)} registered)")
    return SCENARIOS[name]


def match_scenarios(patterns) -> list:
    """Expand names / fnmatch patterns into sorted scenario names; raises
    on a pattern matching nothing (silent empty grids hide typos)."""
    names = []
    for pat in patterns:
        hit = sorted(n for n in SCENARIOS if fnmatch.fnmatch(n, pat))
        if not hit:
            raise KeyError(f"pattern {pat!r} matches no scenario; see --list")
        names.extend(h for h in hit if h not in names)
    return names


def _register_paper_grid():
    """The paper's Section 7 grid: every strategy in REGISTRY against every
    availability process, uniform sampling, Dirichlet(0.1) heterogeneity.
    The markov column is the beyond-paper F3AST setting (Ribero et al.);
    cells are named ``<strategy>/<kind>``."""
    for strat in sorted(REGISTRY):
        for kind in KINDS:
            note = ("F3AST-style Gilbert-Elliott availability "
                    "(Ribero et al.)" if kind == "markov" else
                    "paper Section 7 dynamics")
            register_scenario(Scenario(name=f"{strat}/{kind}",
                                       strategy=strat, kind=kind, note=note))
    # epoch-permutation sampler cells for the headline strategy: same
    # dynamics, exactly-once-per-epoch data order (PR 3 sampler substrate)
    for kind in KINDS:
        register_scenario(Scenario(
            name=f"fedawe/{kind}+epoch", strategy="fedawe", kind=kind,
            sampling="epoch", note="epoch-permutation device sampler"))
    # heterogeneity ablations (Section 7's Dirichlet sweep, sine dynamics)
    for alpha, tag in ((100.0, "iid"), (0.3, "dir03"), (0.05, "dir005")):
        register_scenario(Scenario(
            name=f"fedawe/sine@{tag}", strategy="fedawe", kind="sine",
            alpha=alpha, note=f"Dirichlet alpha={alpha} heterogeneity"))
    # Assumption-1 floor ablation: the clamp keeps every client reachable
    register_scenario(Scenario(
        name="fedawe/interleaved_sine@floor", strategy="fedawe",
        kind="interleaved_sine", delta_floor=0.05,
        note="delta_floor=0.05 keeps Assumption 1 in the dynamics"))

    # fault-injection cells (core/faults.py): deployment-grade failure
    # modes composed onto the same availability interface
    register_scenario(Scenario(
        name="fig2_midround_dropout", strategy="fedawe", nu_corr=True,
        upload_survival=0.7, sanitize=True,
        note="Fig.2 nu-correlated availability + 30% mid-round dropout "
             "+ sanitization"))
    register_scenario(Scenario(
        name="blackout_cluster", strategy="fedawe", kind="sine",
        blackout_start=4, blackout_len=4, blackout_every=12,
        blackout_cluster=0,
        note="recurring 4-round blackout of data cluster 0 "
             "(dominant-label targeting)"))
    register_scenario(Scenario(
        name="trace_diurnal", strategy="fedawe", fault_trace="diurnal",
        note="replay a recorded-style diurnal [T, m] availability trace "
             "bit-exactly"))
    # mid-round dropout column: every strategy against the same failure
    for strat in sorted(REGISTRY):
        register_scenario(Scenario(
            name=f"{strat}/midround", strategy=strat, kind="sine",
            upload_survival=0.8, sanitize=True,
            note="20% mid-round upload dropout + sanitization"))

    # semi-async cells (core/staleness.py): stragglers keep computing on
    # stale parameters; uploads land d rounds late, bounded by tau_max
    for strat in sorted(REGISTRY):
        register_scenario(Scenario(
            name=f"{strat}/stale_d2", strategy=strat, kind="sine",
            stale_max=2, stale_kind="det", stale_delay=2,
            note="deterministic 2-round straggler delay, sine dynamics"))
    register_scenario(Scenario(
        name="fedawe/stale_geom", strategy="fedawe", kind="sine",
        stale_max=4, stale_kind="geom", stale_p=0.5,
        note="geometric upload delays, tau_max=4 bound"))
    register_scenario(Scenario(
        name="fedawe/stale_trace", strategy="fedawe", kind="sine",
        stale_max=4, stale_kind="trace",
        note="replayed staircase per-client delay trace, tau_max=4"))
    register_scenario(Scenario(
        name="fedawe/stale_d2+midround", strategy="fedawe", kind="sine",
        stale_max=2, stale_kind="det", stale_delay=2,
        upload_survival=0.8, sanitize=True,
        note="semi-async delays composed with 20% mid-round dropout "
             "+ sanitization at delivery"))
    register_scenario(Scenario(
        name="fedar/semi_async", strategy="fedar", kind="sine",
        stale_max=4, stale_kind="geom", stale_p=0.5, stale_gamma=0.7,
        note="FedAR rectification baseline (Jiang et al. 2024): "
             "geometric delays, gamma**d delivery discount"))

    GRIDS.update({
        # speedup-vs-availability comparison (Yan et al. 2020 framing)
        "speedup-sine": ["fedawe/sine", "fedawe_m/sine",
                         "fedavg_active/sine", "fedavg_known_p/sine",
                         "fedau/sine", "mifa/sine", "fedvarp/sine"],
        # Fig. 3-style non-stationarity sweep for the headline strategies
        "nonstationary": [f"{s}/{k}" for s in ("fedawe", "fedavg_active",
                                               "fedau")
                          for k in ("staircase", "sine",
                                    "interleaved_sine")],
        # the F3AST/Ribero Markov column, every strategy
        "f3ast-markov": [f"{s}/markov" for s in sorted(REGISTRY)],
        # the full Section 7 grid
        "paper-sec7": [f"{s}/{k}" for s in sorted(REGISTRY)
                       for k in ("stationary", "staircase", "sine",
                                 "interleaved_sine")],
        # fault-injection stress cells: the named failure modes plus the
        # every-strategy mid-round dropout column
        "faults": (["fig2_midround_dropout", "blackout_cluster",
                    "trace_diurnal"]
                   + [f"{s}/midround" for s in sorted(REGISTRY)]),
        # semi-async stress cells: every strategy under deterministic
        # delays, plus the delay-distribution / composition / FedAR cells
        "staleness": ([f"{s}/stale_d2" for s in sorted(REGISTRY)]
                      + ["fedawe/stale_geom", "fedawe/stale_trace",
                         "fedawe/stale_d2+midround", "fedar/semi_async"]),
    })


_register_paper_grid()


# ---------------------------------------------------------------------------
# vmapped multi-seed executor driver
# ---------------------------------------------------------------------------

def build_seed_batch(cfg: FLConfig, template, base_rng, data_key,
                     init_sampler_state, store, n_seeds: int, *,
                     template_fn=None, model_rng=None, seed_ids=None,
                     fault=None, stale=None):
    """Stacked per-seed carry for ``make_seeds_chunk_fn``.

    Seed replicate ``j`` is initialized EXACTLY as an independent
    single-seed run with ``rng_j = fold_in(base_rng, j)`` and
    ``data_key_j = fold_in(data_key, j)`` would be — states are built
    one-by-one and tree-stacked (bitwise-preserving), which is the root
    of the multi-seed parity guarantee.

    Template modes (the replication semantics):

      * shared (``template_fn=None``, default): every replicate starts
        from the one ``template`` passed in — seeds vary only the
        stochastic draws (availability, local-SGD noise, batch sampling).
        Bit-compatible with the original executor, which the parity tests
        pin down.
      * full (``template_fn`` given): paper-style fully independent
        replicates — seed ``j``'s model parameters are re-initialized
        from ``template_fn(fold_in(model_rng, j))`` (``model_rng``
        defaults to ``base_rng``), so the replicates differ in their init
        point too, exactly as S independently-seeded runs would.

    ``seed_ids`` (default ``range(n_seeds)``) names which replicate id
    each stacked row carries: row ``i`` uses fold-in id ``seed_ids[i]``
    throughout (state rng, data key, template).  Permuting ``seed_ids``
    therefore permutes the per-seed results identically — the
    independence property the hypothesis sweep checks.

    ``fault`` (a ``faults.init_fault_state`` pytree, or None) is the
    fault-injection carry — the SAME replay trace / cluster labels for
    every replicate (seeds vary the stochastic draws, not the recorded
    failure pattern), stacked over the seed axis like the rest of the
    state.  ``stale`` (a ``staleness.init_staleness_state`` pytree, or
    None) is the semi-async pending-update ring buffer, threaded the
    same way: every replicate starts from the same (empty) buffer and
    the per-seed delay draws diverge through the state rng.

    Returns ``(states, sampler_states, data_keys)`` with ``[S, ...]``
    leaves (``sampler_states`` is ``{}`` under uniform sampling).
    """
    ids = list(range(n_seeds)) if seed_ids is None else \
        [int(j) for j in seed_ids]
    assert len(ids) == n_seeds, (ids, n_seeds)
    if model_rng is None:
        model_rng = base_rng

    def tmpl(j):
        if template_fn is None:
            return template
        return template_fn(jax.random.fold_in(model_rng, j))

    states = stack_seeds([
        init_fl_state(jax.random.fold_in(base_rng, j), cfg, tmpl(j),
                      fault=fault, stale=stale)
        for j in ids])
    if seed_ids is None:
        data_keys = seed_data_keys(data_key, n_seeds)
    else:
        data_keys = jnp.stack([jax.random.fold_in(data_key, j)
                               for j in ids])
    sampler_states = init_seed_sampler_states(init_sampler_state, store,
                                              data_keys)
    return states, sampler_states, data_keys


def seed_chunk_shardings(mesh, fl: FLConfig, round_fn, sample_fn, n_seeds,
                         states, sampler_states, store, data_keys):
    """``(in_shardings, out_shardings)`` for the LIVE S-batched executor
    jit on ``mesh`` — ``sharding/rules.seed_pspecs`` threaded through the
    running ``make_seeds_chunk_fn``, not just the dry-run.

    The seed axis rides the mesh's dedicated ``'seed'`` axis when there is
    one (``launch/mesh.make_seed_mesh``'s ``('seed','pod','data')``), in
    which case the inner ``[m, N]`` client placement over ``('pod','data')``
    SURVIVES underneath it; on a seed-less mesh the seed axis takes over
    the client axes and the displaced inner placement is stripped (the
    PR 4 trade).  The store's index matrix/counts stay on the client axes,
    backing arrays and the per-seed data keys replicate, and metrics
    (tiny ``[S, K]`` scalars) replicate.  Flat substrate only — the spec
    rules key off the ``[m, N]`` layout.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import mesh_axis_sizes
    from repro.sharding import (flat_pspecs, sampler_pspecs, seed_axes_for,
                                seed_pspecs)

    assert fl.flat_state, \
        "seed_chunk_shardings needs the flat [m, N] substrate"
    ax = mesh_axis_sizes(mesh)
    multi_pod = "pod" in ax
    sa = seed_axes_for(mesh)
    ca = ("pod", "data") if multi_pod else ("data",)

    def ns(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    inner_state = jax.eval_shape(lambda t: index_seed(t, 0), states)
    inner_sampler = jax.eval_shape(lambda t: index_seed(t, 0),
                                   sampler_states)
    state_spec = seed_pspecs(
        flat_pspecs(mesh, inner_state, multi_pod=multi_pod), seed_axes=sa)
    sampler_spec = seed_pspecs(
        sampler_pspecs(mesh, inner_sampler, fl.m, multi_pod=multi_pod),
        seed_axes=sa)
    store_spec = dict(
        arrays=jax.tree.map(lambda v: P(*([None] * v.ndim)),
                            store["arrays"]),
        idx=P(ca, None),
        counts=P(ca),
    )
    # metrics structure comes from an abstract trace of the (unjitted)
    # executor — generic over whatever metric dict round_fn returns
    probe = make_seeds_chunk_fn(fl, round_fn, sample_fn, 1, n_seeds,
                                donate=False, jit=False)
    metrics_sds = jax.eval_shape(probe, states, sampler_states, store,
                                 data_keys)[2]
    metrics_spec = jax.tree.map(lambda x: P(*([None] * x.ndim)),
                                metrics_sds)
    in_sh = (ns(state_spec), ns(sampler_spec), ns(store_spec),
             NamedSharding(mesh, P(None, None)))
    out_sh = (ns(state_spec), ns(sampler_spec), ns(metrics_spec))
    return in_sh, out_sh


def build_seed_executor(fl: FLConfig, round_fn, sample_fn, n_seeds, *,
                        mesh=None, states=None, sampler_states=None,
                        store=None, data_keys=None):
    """``builder(k) -> `` S-batched chunk executor for any chunk length
    ``k`` (the same builder serves the full-K chunks and the ``T % K``
    tail, so the tail keeps the caller's placement).  With ``mesh``, the
    executor jit carries ``seed_chunk_shardings``' in/out shardings on top
    of the usual donation; without, it is the plain donated executor.

    The builder exposes the resolved input shardings as
    ``builder.in_shardings`` (None without a mesh) — feed them to
    ``place_seed_batch`` so the FIRST dispatch already sees mesh-committed
    carries.  A freshly built (default-placement) carry and the donated
    mesh-sharded output of the previous chunk are two distinct jit input
    signatures, so skipping the placement compiles the same executor twice
    (the old ``compile_count/chunked_seeds_mesh = 2``)."""
    if mesh is None:
        def builder(k):
            return make_seeds_chunk_fn(fl, round_fn, sample_fn, k, n_seeds)
        builder.in_shardings = None
        return builder
    in_sh, out_sh = seed_chunk_shardings(
        mesh, fl, round_fn, sample_fn, n_seeds, states, sampler_states,
        store, data_keys)

    def builder(k):
        return make_seeds_chunk_fn(fl, round_fn, sample_fn, k, n_seeds,
                                   in_shardings=in_sh,
                                   out_shardings=out_sh)
    builder.in_shardings = in_sh
    return builder


def place_seed_batch(in_shardings, states, sampler_states, store,
                     data_keys):
    """Commit a freshly built seed batch onto the executor's input
    shardings (``build_seed_executor``'s ``builder.in_shardings``) BEFORE
    the first dispatch.  ``jnp.stack``-built carries are uncommitted
    default-placement arrays; dispatching them as-is keys a second jit
    signature next to the steady-state one whose donated inputs carry the
    mesh sharding.  ``device_put`` is bitwise-preserving, so parity is
    untouched.  No-op when ``in_shardings`` is None (mesh-less builder)."""
    if in_shardings is None:
        return states, sampler_states, store, data_keys
    return jax.device_put((states, sampler_states, store, data_keys),
                          in_shardings)


def _resolve_chunk_rounds(chunk_rounds, rounds):
    """Validated dispatch chunk length: ``chunk_rounds`` clamped to the
    run length.  Zero or negative values raise — the multi-seed and
    packed drivers are ALWAYS chunked, and the old ``int(chunk_rounds)
    or 8`` fallback silently turned an explicit ``--chunk-rounds 0`` into
    K=8 (CLIs that want an auto default resolve it before calling)."""
    K = int(chunk_rounds)
    if K <= 0:
        raise ValueError(
            f"chunk_rounds={chunk_rounds} must be >= 1: the multi-seed "
            "drivers are always chunked (0 used to silently become 8; "
            "resolve any auto default at the CLI layer instead)")
    return min(K, int(rounds))


def _append_seed_records(histories, metrics, k, done, n_seeds):
    """Append one fetched ``[S, k]`` metrics blob to per-seed histories
    as per-round dicts (``{"t": done+i, <metric>: float, ...}``).  The
    ONE record builder shared by the unpacked (``run_seed_rounds``) and
    packed (``run_packed_group``) drivers — their bit-parity guarantee
    includes the history records, so the construction must not drift."""
    for j in range(n_seeds):
        for i in range(k):
            rec = {key: float(v[j][i]) for key, v in metrics.items()}
            rec["t"] = done + i
            histories[j].append(rec)


def run_seed_rounds(states, chunk_fn, T, K, *, sampler_states, store,
                    data_keys, n_seeds, make_tail_fn=None, eval_fn=None,
                    eval_every=0, log_every=0, ckpt_fn=None, ckpt_every=0):
    """Drive the S-batched executor for T rounds in ceil(T/K) dispatches.

    The seed-axis analogue of ``engine.run_rounds(chunk_rounds=K)``: each
    dispatch advances every replicate K rounds and fetches the stacked
    ``[S, K]`` metrics with one ``jax.device_get``.  ``eval_fn`` (taking a
    single-seed ``FLState``) runs per seed at the first chunk boundary at
    or past each ``eval_every`` multiple, on ``index_seed(states, j)``.
    ``ckpt_fn(states, done, sampler_states)`` fires likewise per
    ``ckpt_every`` with BOTH seed-stacked carries in hand — feed it
    ``checkpointing.save_run_state`` for a mid-grid resumable checkpoint
    (the donated carries are consumed by the next dispatch, so the hook
    is the only place to capture them).  A ``T % K`` tail needs
    ``make_tail_fn(k)`` (an S-batched executor for the shorter chunk)
    when T is not a multiple of K.

    Returns ``(states, histories)`` — one history (list of per-round
    metric dicts) per seed.
    """
    if T % K and make_tail_fn is None:
        # fail BEFORE the first dispatch (mirrors _run_rounds_chunked's
        # tail footgun): discovering the missing tail builder after T-T%K
        # rounds would throw away all completed seed-replicate work
        raise ValueError(
            f"T={T} is not a multiple of chunk_rounds={K}: pass "
            "make_tail_fn(k) to build the S-batched tail executor, or "
            "make T a multiple of K")
    histories = [[] for _ in range(n_seeds)]
    tail_fn, done, step = None, 0, 0
    warmed = set()
    while done < T:
        k = min(K, T - done)
        if k == K:
            f = chunk_fn
        else:
            tail_fn = tail_fn or make_tail_fn(k)
            f = tail_fn
        # the host spans of engine._run_rounds_chunked, under its names
        with jax.profiler.StepTraceAnnotation("fl_chunk", step_num=step):
            with jax.profiler.TraceAnnotation("fl_chunk_dispatch"):
                states, sampler_states, metrics = _dispatch(
                    f, warmed, states, sampler_states, store, data_keys)
            with jax.profiler.TraceAnnotation("fl_chunk_fetch"):
                metrics = jax.device_get(metrics)  # ONE host sync a chunk
            with jax.profiler.TraceAnnotation("fl_chunk_records"):
                _append_seed_records(histories, metrics, k, done, n_seeds)
            done += k
            with jax.profiler.TraceAnnotation("fl_chunk_hooks"):
                if eval_fn is not None and _crossed(done, k, eval_every):
                    for j in range(n_seeds):
                        histories[j][-1].update(
                            eval_fn(index_seed(states, j)))
                if ckpt_fn is not None and _crossed(done, k, ckpt_every):
                    ckpt_fn(states, done, sampler_states)
                if _crossed(done, k, log_every):
                    mean_loss = sum(h[-1].get("loss", float("nan"))
                                    for h in histories) / n_seeds
                    print(f"[round {done:5d}] seeds={n_seeds} "
                          f"mean_loss={mean_loss:.4f}")
        step += 1
    return states, histories


def run_multi_seed(fl: FLConfig, round_fn, template, ds, *, sampling,
                   batch, seeds, rounds, chunk_rounds, rng, data_key,
                   eval_fn=None, eval_every=0, log_every=0, mesh=None,
                   template_fn=None, fault=None, stale=None):
    """THE multi-seed driver (used by both this module's ``run_scenario``
    and ``train.py --seeds``): device store + stateful sampler + stacked
    per-seed carry + S-batched executor, end to end.

    ``chunk_rounds`` must be >= 1 (``_resolve_chunk_rounds`` raises on
    the old silent 0 -> 8 fallback); K is clamped to ``rounds`` and a
    ``T % K`` tail executor is built automatically.  ``mesh`` (e.g.
    ``launch/mesh.make_seed_mesh``'s ``('seed','pod','data')``) threads
    the live ``seed_chunk_shardings`` through the executor jit and
    commits the initial carries onto them (``place_seed_batch``) so the
    warm-up dispatch compiles the same program as steady state;
    ``template_fn`` switches shared-template replication to paper-style
    per-seed model re-init (see ``build_seed_batch``).  Returns
    ``(states, histories, finals)`` — the seed-stacked final ``FLState``,
    one metric history per seed, and (when ``eval_fn`` is given) one
    final-eval dict per seed via ``index_seed``.
    """
    K = _resolve_chunk_rounds(chunk_rounds, rounds)
    store = ds.device_store()
    init_fn, sample_fn = make_device_sampler(
        fl.m, fl.s, batch, mode=sampling,
        min_count=min(len(ix) for ix in ds.client_indices),
        emit="cols" if fl.sparse_cohort else "batches")
    states, sampler_states, data_keys = build_seed_batch(
        fl, template, rng, data_key, init_fn, store, seeds,
        template_fn=template_fn, fault=fault, stale=stale)
    builder = build_seed_executor(fl, round_fn, sample_fn, seeds,
                                  mesh=mesh, states=states,
                                  sampler_states=sampler_states,
                                  store=store, data_keys=data_keys)
    states, sampler_states, store, data_keys = place_seed_batch(
        builder.in_shardings, states, sampler_states, store, data_keys)
    states, histories = run_seed_rounds(
        states, builder(K), rounds, K, sampler_states=sampler_states,
        store=store, data_keys=data_keys, n_seeds=seeds,
        make_tail_fn=builder,
        eval_fn=eval_fn, eval_every=eval_every, log_every=log_every)
    finals = ([eval_fn(index_seed(states, j)) for j in range(seeds)]
              if eval_fn is not None else [])
    return states, histories, finals


def _pad_m_config(sc: Scenario, fl: FLConfig, base_p, pad_m: int, *,
                  has_fault, has_stale):
    """Widen a cell's client axis from ``fl.m`` to ``pad_m`` with
    zero-availability-mass padding rows (the ``m`` half of bucket
    padding).

    Padded clients carry ``base_p = 0``: every non-Markov availability
    kind draws ``mask = uniform < p`` so they NEVER activate, and the
    Markov chain's turn-on rate scales with ``base_p`` so once off they
    stay off (``build_cell`` zeroes their all-on init rows).  Inactive
    clients aggregate to exactly zero through the existing mask path —
    every strategy weight clips its denominator, so ``p = 0`` rows are
    inert, not NaN.  Eligibility is strict because the parity contract
    is conservative: uniform sampling only (epoch permutations are
    m-shaped draws), no Assumption-1 floor (``delta_floor`` would
    resurrect the padding rows), no fault/staleness carries (their
    traces and ring buffers are sized to the real ``m``), flat substrate
    only.  NOTE: padding ``m`` changes the cell's rng stream shapes
    (``split(key, m)`` etc.), so a padded cell is bit-identical to the
    UNPADDED-DRIVER run of the same padded config — not to the original
    ``m``-client cell.  Cap-only padding (``data.federated.pad_store``)
    is the stronger, draw-preserving tier.
    """
    if pad_m == fl.m:
        return fl, base_p
    assert pad_m > fl.m, (pad_m, fl.m)
    if sc.sampling != "uniform":
        raise ValueError(
            f"pad_m: cell {sc.name!r} uses {sc.sampling!r} sampling; "
            "only uniform-mode cells can absorb padded clients")
    if sc.delta_floor > 0:
        raise ValueError(
            f"pad_m: cell {sc.name!r} has delta_floor={sc.delta_floor}; "
            "the Assumption-1 clamp would give padded clients non-zero "
            "availability mass")
    if has_fault or has_stale:
        raise ValueError(
            f"pad_m: cell {sc.name!r} carries fault/staleness state "
            "sized to the real client count; padding is not supported")
    if not fl.flat_state:
        raise ValueError(f"pad_m: cell {sc.name!r} needs flat_state")
    base_p = jnp.concatenate(
        [base_p, jnp.zeros((pad_m - fl.m,), base_p.dtype)])
    return dataclasses.replace(fl, m=pad_m), base_p


def _cell_task(sc: Scenario, *, m, s, batch, n_samples, preset, seed,
               use_kernel, rounds=0, pad_m=0, client_shards=1):
    """Materialize one cell's task + round function: ``(fl, round_fn,
    ds, eval_fn, init_fn, fault_state, stale_state)``.

    The fault knobs resolve here: ``nu_corr`` swaps the data-derived
    ``base_p`` for the adversarial ν-correlated one, a ``fault_trace``
    simulates its ``[rounds, m]`` replay trace (keyed ``seed + 2`` so it
    is independent of the model/data streams), and blackout cells derive
    their cluster labels from the task's ν.  ``fault_state`` is None for
    fault-free cells.  Semi-async knobs resolve here too: ``stale_max>0``
    builds the ``[tau_max, m, N]`` pending-update ring buffer (and, for
    ``stale_kind='trace'``, a staircase delay trace keyed ``seed + 3``);
    ``stale_state`` is None for synchronous cells.  ``pad_m > m`` widens
    the client axis with zero-availability padding rows BEFORE the round
    function closes over ``base_p`` (see ``_pad_m_config``) — the data
    partition keeps ``m`` real clients.  ``client_shards`` is the number
    of devices a seed mesh splits the client rows over
    (``sharding.mesh_client_shards``), passed on to ``make_round_fn``.
    """
    # lazy import: train.py imports this module for --scenario/--seeds
    from repro.core import faults, staleness
    from repro.core.flatten import FlatSpec
    from repro.launch import train as train_mod

    args = argparse.Namespace(seed=seed, n_samples=n_samples, m=m,
                              alpha=sc.alpha, batch=batch)
    rng = jax.random.PRNGKey(seed)
    build = (train_mod.build_image_task if preset == "image"
             else train_mod.build_lm_task)
    params, loss_fn, ds, base_p, eval_fn, init_fn = build(args, rng)
    if sc.nu_corr:
        base_p = faults.adversarial_probs_from_nu(ds.nu)
    fl = FLConfig(m=m, s=s, eta_l=sc.eta_l, eta_g=sc.eta_g,
                  strategy=sc.strategy, flat_state=sc.flat_state,
                  use_kernel=use_kernel)
    fc = sc.fault()
    fault_state = None
    if fc is not None and fc.needs_state:
        trace = None
        if fc.trace:
            assert rounds > 0, \
                f"trace cell {sc.name!r} needs the run length for its trace"
            trace = faults.diurnal_trace(jax.random.PRNGKey(seed + 2),
                                         base_p, rounds)
        clusters = (faults.clusters_from_nu(ds.nu)
                    if fc.blackout_len > 0 else None)
        fault_state = faults.init_fault_state(fc, trace=trace,
                                              clusters=clusters)
    stcfg = sc.staleness()
    stale_state = None
    if stcfg is not None and stcfg.needs_state:
        dtrace = None
        if stcfg.kind == "trace":
            assert rounds > 0, \
                f"trace cell {sc.name!r} needs the run length for its trace"
            dtrace = staleness.staircase_delay_trace(
                jax.random.PRNGKey(seed + 3), m, rounds)
        stale_state = staleness.init_staleness_state(
            stcfg, FlatSpec.from_tree(params).size, m, dtrace=dtrace)
    if pad_m:
        fl, base_p = _pad_m_config(sc, fl, base_p, pad_m,
                                   has_fault=fault_state is not None,
                                   has_stale=stale_state is not None)
    rf = make_round_fn(fl, loss_fn, {}, sc.availability(), base_p,
                       fault_cfg=fc, staleness_cfg=stcfg,
                       client_shards=client_shards)
    return fl, rf, params, ds, eval_fn, init_fn, fault_state, stale_state


def _cell_record(sc: Scenario, *, seeds, rounds, chunk_rounds, finals,
                 histories):
    return dict(
        scenario=sc.name, strategy=sc.strategy, dynamics=sc.kind,
        sampling=sc.sampling, alpha=sc.alpha, seeds=seeds, rounds=rounds,
        chunk_rounds=chunk_rounds, note=sc.note,
        final=analysis.seed_summary(finals),
        curves=analysis.aggregate_seed_histories(histories),
        histories=histories,
    )


def run_scenario(sc: Scenario, *, seeds=4, rounds=24, chunk_rounds=8,
                 m=16, s=3, batch=8, n_samples=4000, preset="image",
                 seed=0, eval_every=0, use_kernel=False, log_every=0,
                 mesh=None, replicate="shared"):
    """Run one grid cell: S seed replicates of ``rounds`` rounds, advanced
    K rounds per dispatch by the vmapped multi-seed executor.

    ``mesh`` threads the live seed-mesh shardings through the executor
    jit (``seed_chunk_shardings``); ``replicate='full'`` re-initializes
    the model per seed (see ``build_seed_batch``).  Returns the cell
    record: per-seed final evals, their mean±std (``final``), mean±std
    metric curves (``curves``), and the raw per-seed ``histories``.
    """
    K = _resolve_chunk_rounds(chunk_rounds, rounds)   # fail BEFORE task build
    fl, rf, params, ds, eval_fn, init_fn, fault_state, stale_state = \
        _cell_task(
            sc, m=m, s=s, batch=batch, n_samples=n_samples, preset=preset,
            seed=seed, use_kernel=use_kernel, rounds=rounds,
            client_shards=mesh_client_shards(mesh, seeds=True))
    states, histories, finals = run_multi_seed(
        fl, rf, params, ds, sampling=sc.sampling, batch=batch, seeds=seeds,
        rounds=rounds, chunk_rounds=K, rng=jax.random.PRNGKey(seed),
        data_key=jax.random.PRNGKey(seed + 1), eval_fn=eval_fn,
        eval_every=eval_every, log_every=log_every, mesh=mesh,
        template_fn=init_fn if replicate == "full" else None,
        fault=fault_state, stale=stale_state)
    return _cell_record(sc, seeds=seeds, rounds=rounds, chunk_rounds=K,
                        finals=finals, histories=histories)


# ---------------------------------------------------------------------------
# grid packing: shape-compatible cells -> one donated dispatch stream
# ---------------------------------------------------------------------------

def build_cell(sc: Scenario, *, seeds, rounds, chunk_rounds, m, s, batch,
               n_samples, preset, seed, use_kernel=False,
               replicate="shared", pad_m=0, mesh=None):
    """Build everything one PACKED grid cell needs — task, round/sample
    fns, device store, and the stacked per-seed carry — without running
    it.  The returned dict is the unit ``pack_cells`` groups and
    ``run_packed_grid`` drives.

    ``pad_m > m`` widens the client axis with zero-availability padding
    rows so a smaller cell can share a bucket shape with an ``m = pad_m``
    one (``_pad_m_config`` documents the eligibility rules and the parity
    contract); the padded store rows own one dummy sample each
    (``data.federated.pad_store``) and padded Markov chains start (and
    stay) off.  ``cap_paddable`` in the returned dict marks cells whose
    sampler-cap column ``pack_cells(pad=True)`` may pad bit-exactly.
    ``mesh`` is the seed mesh the cell will run on, if any.
    """
    K = _resolve_chunk_rounds(chunk_rounds, rounds)   # fail BEFORE task build
    fl, rf, params, ds, eval_fn, init_fn, fault_state, stale_state = \
        _cell_task(
            sc, m=m, s=s, batch=batch, n_samples=n_samples, preset=preset,
            seed=seed, use_kernel=use_kernel, rounds=rounds, pad_m=pad_m,
            client_shards=mesh_client_shards(mesh, seeds=True))
    store = ds.device_store()
    if fl.m > m:
        from repro.data.federated import pad_store
        store = pad_store(store, m=fl.m)
    init_sampler, sample_fn = make_device_sampler(
        fl.m, fl.s, batch, mode=sc.sampling,
        min_count=min(len(ix) for ix in ds.client_indices),
        emit="cols" if fl.sparse_cohort else "batches")
    states, sampler_states, data_keys = build_seed_batch(
        fl, params, jax.random.PRNGKey(seed), jax.random.PRNGKey(seed + 1),
        init_sampler, store, seeds,
        template_fn=init_fn if replicate == "full" else None,
        fault=fault_state, stale=stale_state)
    if fl.m > m and sc.kind == "markov":
        # padded clients must START off: base_p = 0 zeroes their turn-on
        # rate, but init_fl_state births the whole chain all-on
        states = states._replace(
            markov=states.markov.at[:, m:].set(0.0))
    return dict(sc=sc, fl=fl, round_fn=rf, sample_fn=sample_fn,
                store=store, states=states, sampler_states=sampler_states,
                data_keys=data_keys, eval_fn=eval_fn, seeds=seeds,
                rounds=rounds, K=K,
                cap_paddable=(sc.sampling == "uniform"))


def _shape_sig(tree):
    """Hashable (path, shape, dtype) signature of a pytree of arrays —
    the grouping key of the packing layer."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return (str(treedef),) + tuple(
        (jax.tree_util.keystr(kp), tuple(int(d) for d in x.shape),
         str(x.dtype)) for kp, x in flat)


def pack_cells(cells, *, pad=False):
    """Group built cells by array-shape signature — same model/m/N
    shapes, same strategy-memory shapes, same sampler-state shapes, same
    S/K/T — preserving input order within and across groups.  Every group
    runs as ONE donated dispatch stream (``engine.make_grid_chunk_fn``).

    ``pad=True`` widens the packing with bucket padding + stream merging:

      * near-miss cells — identical signatures except the sampler-cap
        column of the store's ``[m, cap]`` index matrix (per-cell
        Dirichlet partitions: a heterogeneity ablation changes the max
        client shard and nothing else) — are padded in place up to their
        bucket's max cap (``data.federated.pad_store``).  Cap padding is
        bit-exact for uniform-mode cells (the sampler's draws are
        count-bounded and the gather never reads a padded column), so a
        padded cell's results are identical to its unpadded run; cells
        without ``cap_paddable`` are left untouched.
      * groups are then merged down to ONE stream per (seeds, K, rounds):
        ``make_grid_chunk_fn`` takes C-tuples of per-cell carries and
        never requires cells to share shapes, so the whole Section 7 grid
        (one shape signature per strategy family) advances as a single
        dispatch stream.  Padding still matters on top of the merge — it
        collapses near-miss cells onto one subgraph shape, so XLA (and
        the persistent compilation cache, ``launch/compilecache``) sees
        one program where it would otherwise compile one per alpha.

    Client-axis (``m``) padding enters upstream through
    ``build_cell(pad_m=...)`` — it has to rebuild the round function with
    zero-mass ``base_p`` rows, which only the cell builder can do; cells
    padded there group here by their padded signature like any other.
    """
    if pad:
        from repro.data.federated import pad_store
        buckets: dict = {}
        for c in cells:
            if not c.get("cap_paddable"):
                continue
            # bucket key = full signature with the cap column abstracted
            # away (idx[:, :1] keeps treedef/dtype/m, normalizes cap)
            key = (_shape_sig(c["states"]), _shape_sig(c["sampler_states"]),
                   _shape_sig(dict(c["store"],
                                   idx=c["store"]["idx"][:, :1])),
                   c["seeds"], c["K"], c["rounds"])
            buckets.setdefault(key, []).append(c)
        for bucket in buckets.values():
            cap = max(c["store"]["idx"].shape[1] for c in bucket)
            for c in bucket:
                short = cap - c["store"]["idx"].shape[1]
                if short:
                    c["store"] = pad_store(c["store"], cap=cap)
                    c["padded_cap"] = short
    groups: dict = {}
    for c in cells:
        sig = ((c["seeds"], c["K"], c["rounds"]) if pad else
               (_shape_sig(c["states"]), _shape_sig(c["sampler_states"]),
                _shape_sig(c["store"]), c["seeds"], c["K"], c["rounds"]))
        groups.setdefault(sig, []).append(c)
    return list(groups.values())


def grid_chunk_shardings(mesh, cells):
    """Per-cell ``seed_chunk_shardings`` assembled into the C-tuple
    argument structure of ``make_grid_chunk_fn``: the packed jit takes
    ``(states_t, sampler_states_t, stores_t, data_keys_t)`` — each a
    C-tuple over cells — so its in/out shardings are the per-cell
    sharding trees zipped the same way.  Every cell gets the SAME mesh
    placement it would get unpacked (``seed_pspecs`` over
    ``('seed','pod','data')``), which is what makes packed × mesh runs
    bit-identical to their unpacked counterparts."""
    per = [seed_chunk_shardings(mesh, c["fl"], c["round_fn"],
                                c["sample_fn"], c["seeds"], c["states"],
                                c["sampler_states"], c["store"],
                                c["data_keys"]) for c in cells]
    in_sh = tuple(zip(*(p[0] for p in per)))
    out_sh = tuple(zip(*(p[1] for p in per)))
    return in_sh, out_sh


def run_packed_group(cells, *, mesh=None, eval_every=0, log_every=0):
    """Drive one packed group: ceil(T/K) packed dispatches, each
    advancing every cell x seed x round in the group.  Per-cell results
    are identical to the unpacked ``run_seed_rounds`` drive (the packed
    jit unrolls the same per-cell subgraphs).  ``mesh`` threads per-cell
    seed-mesh shardings through the packed jit
    (``grid_chunk_shardings``) and commits the freshly built carries onto
    them before the first dispatch — one jit signature, warm-up included
    (same placement rule as ``place_seed_batch``).  Returns ``(states_t,
    histories_t)`` — per-cell seed-stacked states and per-cell, per-seed
    metric histories."""
    assert cells
    seeds, K, T = cells[0]["seeds"], cells[0]["K"], cells[0]["rounds"]
    assert all(c["seeds"] == seeds and c["K"] == K and c["rounds"] == T
               for c in cells), "pack_cells groups cells by (S, K, T)"
    pairs = [(c["round_fn"], c["sample_fn"]) for c in cells]
    states_t = tuple(c["states"] for c in cells)
    sampler_t = tuple(c["sampler_states"] for c in cells)
    stores_t = tuple(c["store"] for c in cells)
    keys_t = tuple(c["data_keys"] for c in cells)
    in_sh = out_sh = None
    if mesh is not None:
        in_sh, out_sh = grid_chunk_shardings(mesh, cells)
        states_t, sampler_t, stores_t, keys_t = jax.device_put(
            (states_t, sampler_t, stores_t, keys_t), in_sh)

    def make_packed(k):
        # ONE builder for the full-K chunks AND the T % K tail: the tail
        # used to be rebuilt without shardings, silently dropping the
        # mesh placement for the last dispatch
        return make_grid_chunk_fn(pairs, k, seeds, in_shardings=in_sh,
                                  out_shardings=out_sh)

    packed = make_packed(K)
    tail_fn = None
    histories = [[[] for _ in range(seeds)] for _ in cells]
    done, step = 0, 0
    warmed = set()
    while done < T:
        k = min(K, T - done)
        if k == K:
            f = packed
        else:
            tail_fn = tail_fn or make_packed(k)
            f = tail_fn
        # the host spans of engine._run_rounds_chunked, under its names
        with jax.profiler.StepTraceAnnotation("fl_chunk", step_num=step):
            with jax.profiler.TraceAnnotation("fl_chunk_dispatch"):
                states_t, sampler_t, metrics_t = _dispatch(
                    f, warmed, states_t, sampler_t, stores_t, keys_t)
            with jax.profiler.TraceAnnotation("fl_chunk_fetch"):
                metrics_t = jax.device_get(metrics_t)  # ONE host sync
            with jax.profiler.TraceAnnotation("fl_chunk_records"):
                for ci, metrics in enumerate(metrics_t):
                    _append_seed_records(histories[ci], metrics, k, done,
                                         seeds)
            done += k
            with jax.profiler.TraceAnnotation("fl_chunk_hooks"):
                if _crossed(done, k, eval_every):
                    for ci, c in enumerate(cells):
                        if c["eval_fn"] is None:
                            continue
                        for j in range(seeds):
                            histories[ci][j][-1].update(
                                c["eval_fn"](index_seed(states_t[ci], j)))
                if _crossed(done, k, log_every):
                    print(f"[round {done:5d}] packed group: {len(cells)} "
                          f"cells x {seeds} seeds", flush=True)
        step += 1
    return states_t, histories


def run_packed_grid(names, *, seeds=4, rounds=24, chunk_rounds=8, m=16,
                    s=3, batch=8, n_samples=4000, preset="image", seed=0,
                    eval_every=0, use_kernel=False, log_every=0,
                    replicate="shared", mesh=None, pad=True):
    """The packed grid driver behind ``--packed``: build every named
    cell, group cells (``pack_cells`` — with ``pad=True``, bucket-padded
    and merged to one stream per (S, K, T)), advance each group as one
    donated dispatch stream, and return the per-cell records in input
    order (same shape as ``run_scenario``'s).  ``mesh`` threads per-cell
    seed-mesh shardings through every packed jit
    (``grid_chunk_shardings``)."""
    cells = [build_cell(get_scenario(n), seeds=seeds, rounds=rounds,
                        chunk_rounds=chunk_rounds, m=m, s=s, batch=batch,
                        n_samples=n_samples, preset=preset, seed=seed,
                        use_kernel=use_kernel, replicate=replicate,
                        mesh=mesh)
             for n in names]
    groups = pack_cells(cells, pad=pad)
    padded = sum(1 for c in cells if c.get("padded_cap"))
    print(f"packed {len(cells)} cells into {len(groups)} dispatch "
          f"stream(s)"
          + (f" ({padded} cap-padded)" if padded else ""), flush=True)
    recs = {}
    for group in groups:
        states_t, hists = run_packed_group(group, mesh=mesh,
                                           eval_every=eval_every,
                                           log_every=log_every)
        for c, st, hs in zip(group, states_t, hists):
            finals = ([c["eval_fn"](index_seed(st, j))
                       for j in range(seeds)]
                      if c["eval_fn"] is not None else [])
            recs[c["sc"].name] = _cell_record(
                c["sc"], seeds=seeds, rounds=rounds, chunk_rounds=c["K"],
                finals=finals, histories=hs)
    return [recs[n] for n in names]


def _cell_row(rec: dict) -> dict:
    """Flatten a cell record into one results-table row (final metrics
    rendered paper-style as ``mean±std``)."""
    row = {k: rec[k] for k in ("scenario", "strategy", "dynamics",
                               "sampling", "seeds", "rounds")}
    for k, v in rec["final"].items():
        row[k] = f"{v['mean']:.4f}±{v['std']:.4f}"
    loss = rec["curves"]["metrics"].get("loss")
    if loss is not None:
        row["last_loss"] = f"{loss['mean'][-1]:.4f}±{loss['std'][-1]:.4f}"
    return row


def _slug(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.experiments",
        description="Run named cells of the paper's experiment grid with "
                    "the vmapped multi-seed executor (one dispatch "
                    "advances all seeds one chunk).")
    ap.add_argument("--scenario", action="append", default=None,
                    metavar="NAME",
                    help="scenario name or fnmatch pattern (e.g. "
                         "'fedawe/sine', 'fedau/*'); repeatable")
    ap.add_argument("--grid", default=None, choices=sorted(GRIDS),
                    help="named sub-grid preset (expands to its scenarios)")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and grids, then exit")
    ap.add_argument("--seeds", type=int, default=4,
                    help="seed replicates per cell, advanced together by "
                         "the S-batched executor")
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--chunk-rounds", type=int, default=8,
                    help="K rounds per dispatch (clamped to --rounds)")
    ap.add_argument("--m", type=int, default=16, help="clients")
    ap.add_argument("--s", type=int, default=3, help="local steps")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n-samples", type=int, default=4000)
    ap.add_argument("--preset", default="image", choices=["image", "lm"])
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed; replicate j uses fold_in(seed, j)")
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--packed", action="store_true",
                    help="grid packing: group shape-compatible cells and "
                         "advance each group as ONE donated dispatch per "
                         "chunk (C cells x S seeds x K rounds), instead "
                         "of one dispatch stream per cell; composes with "
                         "--seed-mesh (per-cell shardings thread through "
                         "the packed jit)")
    ap.add_argument("--no-pad-buckets", action="store_true",
                    help="with --packed: disable bucket padding + stream "
                         "merging and pack strictly shape-identical cells "
                         "only (one stream per shape signature — the "
                         "pre-padding behaviour)")
    ap.add_argument("--compile-cache", default="", metavar="DIR",
                    help="enable jax's persistent compilation cache in "
                         "DIR ('auto' = .jax_cache/ at the checkout root; "
                         "$JAX_COMPILATION_CACHE_DIR, when set, wins — see "
                         "launch/compilecache); warm grid re-runs then "
                         "skip XLA compilation entirely")
    ap.add_argument("--replicate", default="shared",
                    choices=["shared", "full"],
                    help="seed-replication mode: 'shared' starts every "
                         "replicate from one model init (original "
                         "behaviour), 'full' re-initializes the model "
                         "per seed from fold_in(model_rng, j) — the "
                         "paper's fully independent replicates")
    ap.add_argument("--seed-mesh", action="store_true",
                    help="build a ('seed','pod','data') mesh "
                         "(launch/mesh.make_seed_mesh, auto-sized from "
                         "--seeds and the device count) and thread the "
                         "seed_pspecs shardings through the live "
                         "executor jit — per-cell for unpacked runs, "
                         "zipped into C-tuples for --packed groups")
    ap.add_argument("--out-dir", default="results",
                    help="per-cell JSON + the results table land here")
    ap.add_argument("--no-save", action="store_true")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.list:
        for name in sorted(SCENARIOS):
            sc = SCENARIOS[name]
            print(f"{name:40s} {sc.strategy:15s} {sc.kind:17s} "
                  f"{sc.sampling:8s} alpha={sc.alpha:<6g} {sc.note}")
        print()
        for g, names in sorted(GRIDS.items()):
            print(f"grid {g}: {len(names)} cells")
        return []

    patterns = list(args.scenario or [])
    if args.grid:
        patterns.extend(GRIDS[args.grid])
    if not patterns:
        raise SystemExit("nothing to run: pass --scenario and/or --grid "
                         "(or --list)")
    names = match_scenarios(patterns)

    mesh = None
    if args.seed_mesh:
        from repro.launch.mesh import make_seed_mesh
        mesh = make_seed_mesh(args.seeds)
        print(f"seed mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}",
              flush=True)
    if args.compile_cache:
        from repro.launch import compilecache
        print(f"compilation cache: {compilecache.enable(args.compile_cache)}",
              flush=True)

    if args.packed:
        recs = run_packed_grid(
            names, seeds=args.seeds, rounds=args.rounds,
            chunk_rounds=args.chunk_rounds, m=args.m, s=args.s,
            batch=args.batch, n_samples=args.n_samples,
            preset=args.preset, seed=args.seed,
            eval_every=args.eval_every, use_kernel=args.use_kernel,
            log_every=max(1, args.rounds // 4), replicate=args.replicate,
            mesh=mesh, pad=not args.no_pad_buckets)
    else:
        recs = []
        for name in names:
            print(f"=== scenario {name} (seeds={args.seeds}, "
                  f"rounds={args.rounds}) ===", flush=True)
            recs.append(run_scenario(
                get_scenario(name), seeds=args.seeds, rounds=args.rounds,
                chunk_rounds=args.chunk_rounds, m=args.m, s=args.s,
                batch=args.batch, n_samples=args.n_samples,
                preset=args.preset, seed=args.seed,
                eval_every=args.eval_every, use_kernel=args.use_kernel,
                log_every=max(1, args.rounds // 4), mesh=mesh,
                replicate=args.replicate))

    rows = []
    for name, rec in zip(names, recs):
        rows.append(_cell_row(rec))
        if not args.no_save:
            path = os.path.join(args.out_dir, "experiments",
                                _slug(name) + ".json")
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1, default=str)
            print(f"wrote {path}")
    if not args.no_save:
        table = analysis.write_results_table(
            rows, os.path.join(args.out_dir, "experiments_table.md"))
        print(f"wrote {table}")
    for row in rows:
        print(json.dumps(row))
    return rows


if __name__ == "__main__":
    main()
