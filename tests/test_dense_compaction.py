"""Dense flat rounds run local SGD only on the round's available rows,
compacted to the front in blocks of ``engine.LOCAL_SGD_BLOCK``.

Guarantees under test:
  * parity — the compacted round evolves BIT-IDENTICALLY to the full
    ``vmap`` over all ``m`` rows (global, client stack, tau, strategy
    extras and every metric but ``n_computed``), for empty, partial,
    block-edge and full participation, with the echo kernel on and off,
    and composed with faults and staleness;
  * the counter — ``n_computed`` is the ``ceil(n / block) * block`` rows
    the trips ran for ``n`` available clients;
  * the build — a round whose client rows are split over devices
    (``client_shards > 1``) keeps one ``vmap`` and no loop; any other
    dense flat round has the loop, all-on availability included;
  * seeds — under the seed ``vmap`` each replicate equals its
    single-seed chunked run.
"""
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AvailabilityCfg, FaultCfg, FLConfig, StalenessCfg,
                        engine, index_seed, init_fl_state,
                        init_staleness_state, make_round_fn,
                        make_seeds_chunk_fn, run_rounds)
from repro.data import device_store, make_device_sampler
from repro.launch.experiments import build_seed_batch, run_seed_rounds
from repro.sharding import mesh_client_shards

M, S, B, DIM = 12, 2, 4, 4
N_FLAT = DIM * DIM + 7
BLOCK = engine.LOCAL_SGD_BLOCK
T, K = 4, 2
# every available client trains: p = 1 on the chosen rows, 0 elsewhere
ALWAYS = AvailabilityCfg(kind="stationary")
SINE = AvailabilityCfg(kind="sine", gamma=0.3)
PERM = np.random.default_rng(3).permutation(M)


def _problem(nan_client=None):
    rng = np.random.default_rng(0)
    n = 8 * M
    x = rng.normal(size=(n, DIM)).astype(np.float32)
    y = rng.normal(size=(n, DIM)).astype(np.float32)
    idx = [np.arange(i, n, M) for i in range(M)]
    if nan_client is not None:
        x[idx[nan_client]] = np.nan
    init_fn, sample_fn = make_device_sampler(M, S, B, mode="uniform")
    return device_store(dict(x=x, y=y), idx), init_fn, sample_fn


def _loss_fn(tr, frozen, batch, rng):
    return (0.5 * jnp.mean((batch["x"] @ tr["w"] - batch["y"]) ** 2)
            + jnp.sum(tr["b"] ** 2))


def _tr0():
    return {"w": jnp.ones((DIM, DIM)) * 0.1, "b": jnp.zeros((7,))}


def _base_p(n_active):
    p = np.zeros((M,), np.float32)
    p[PERM[:n_active]] = 1.0
    return jnp.asarray(p)


def _run(strategy, av, base_p, *, full_vmap=False, use_kernel=False,
         fault_cfg=None, stcfg=None, nan_client=None):
    store, init_fn, sample_fn = _problem(nan_client)
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                   use_kernel=use_kernel, flat_state=True)
    # the sharded build's single vmap is the reference; it runs on one
    # device here all the same
    rf = make_round_fn(cfg, _loss_fn, {}, av, base_p, fault_cfg=fault_cfg,
                       staleness_cfg=stcfg,
                       client_shards=2 if full_vmap else 1)
    stale = (init_staleness_state(stcfg, N_FLAT, M)
             if stcfg is not None and stcfg.needs_state else None)
    state = init_fl_state(jax.random.PRNGKey(0), cfg, _tr0(), stale=stale)
    dk = jax.random.PRNGKey(42)
    return run_rounds(state, rf, None, T, chunk_rounds=K,
                      sample_fn=sample_fn, store=store, data_key=dk,
                      sampler_state=init_fn(store, dk))


def _assert_same(ref, got):
    (sr, hr), (sg, hg) = ref, got
    for a, b in zip(jax.tree.leaves(sr._replace(spec=None)),
                    jax.tree.leaves(sg._replace(spec=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(hr) == len(hg) == T
    for rr, rg in zip(hr, hg):
        assert set(rr) == set(rg)
        assert rr["n_computed"] == M
        for k in set(rr) - {"n_computed"}:
            np.testing.assert_array_equal(rr[k], rg[k], err_msg=k)


def _want_computed(n):
    return math.ceil(n / BLOCK) * BLOCK


@pytest.mark.parametrize("n_active", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, M])
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("strategy", ["fedawe", "mifa", "fedavg_all"])
def test_compacted_round_matches_full_vmap(strategy, use_kernel, n_active):
    base_p = _base_p(n_active)
    ref = _run(strategy, ALWAYS, base_p, full_vmap=True,
               use_kernel=use_kernel)
    got = _run(strategy, ALWAYS, base_p, use_kernel=use_kernel)
    _assert_same(ref, got)
    for r in got[1]:
        assert r["n_active"] == n_active
        assert r["n_computed"] == _want_computed(n_active)


@pytest.mark.parametrize("layers", ["faults", "staleness", "both"])
def test_compacted_round_matches_full_vmap_under_faults_and_staleness(
        layers):
    """Random availability through the fault compute mask (a NaN client
    sanitized away) and busy gating: the blocks see the final mask."""
    fc = FaultCfg(upload_survival=0.7, sanitize=True, norm_cap=50.0) \
        if layers != "staleness" else None
    st = StalenessCfg(tau_max=2, kind="det", delay=1) \
        if layers != "faults" else None
    base_p = jnp.full((M,), 0.5)
    kw = dict(fault_cfg=fc, stcfg=st, nan_client=5)
    ref = _run("fedawe", SINE, base_p, full_vmap=True, **kw)
    got = _run("fedawe", SINE, base_p, **kw)
    _assert_same(ref, got)
    for r in got[1]:
        assert r["n_computed"] == _want_computed(int(r["n_active"]))


def _count_while(jaxpr):
    n = 0
    for eq in jaxpr.eqns:
        n += eq.primitive.name == "while"
        for sub in eq.params.values():
            if hasattr(sub, "jaxpr"):
                n += _count_while(sub.jaxpr)
    return n


@pytest.mark.parametrize("av,fault,shards,want", [
    (ALWAYS, False, 1, 1),
    (ALWAYS, False, 2, 0),
    (SINE, False, 1, 1),
    (SINE, True, 4, 0),
])
def test_only_a_client_sharded_build_keeps_the_single_vmap(av, fault,
                                                           shards, want):
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy="fedawe", flat_state=True)
    fc = FaultCfg(upload_survival=0.7) if fault else None
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.ones((M,)), fault_cfg=fc,
                       client_shards=shards)
    state = init_fl_state(jax.random.PRNGKey(0), cfg, _tr0())
    batches = {"x": jnp.ones((M, S, B, DIM)), "y": jnp.ones((M, S, B, DIM))}
    jaxpr = jax.make_jaxpr(rf)(state, batches)
    assert _count_while(jaxpr.jaxpr) == want
    _, metrics = rf(state, batches)
    if want == 0:
        assert float(metrics["n_computed"]) == M
    elif av is ALWAYS:
        # all-on: 12 rows in two blocks of 8, four of them repeats
        assert float(metrics["n_computed"]) == 2 * BLOCK


SEEDS = 3


def test_seed_vmap_matches_single_seed_runs():
    """Under the seed vmap the loop runs the largest trip count of the
    seeds; each replicate still equals its own single-seed chunked run."""
    store, init_fn, sample_fn = _problem()
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy="fedawe", flat_state=True)
    rf = make_round_fn(cfg, _loss_fn, {}, SINE, jnp.full((M,), 0.7))
    rng, data = jax.random.PRNGKey(0), jax.random.PRNGKey(42)
    singles = []
    for j in range(SEEDS):
        st = init_fl_state(jax.random.fold_in(rng, j), cfg, _tr0())
        dk = jax.random.fold_in(data, j)
        singles.append(run_rounds(st, rf, None, T, chunk_rounds=K,
                                  sample_fn=sample_fn, store=store,
                                  data_key=dk,
                                  sampler_state=init_fn(store, dk)))
    states, sss, dks = build_seed_batch(cfg, _tr0(), rng, data, init_fn,
                                        store, SEEDS)
    states, hists = run_seed_rounds(
        states, make_seeds_chunk_fn(cfg, rf, sample_fn, K, SEEDS), T, K,
        sampler_states=sss, store=store, data_keys=dks, n_seeds=SEEDS)
    computed = set()
    for j in range(SEEDS):
        ref_st, ref_hist = singles[j]
        for a, b in zip(jax.tree.leaves(ref_st._replace(spec=None)),
                        jax.tree.leaves(index_seed(states, j)
                                        ._replace(spec=None))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert ref_hist == hists[j]
        computed |= {r["n_computed"] for r in hists[j]}
    # the seeds' trip counts differ somewhere, so finished seeds idle
    assert len(computed) > 1


def _mesh(**axes):
    return types.SimpleNamespace(axis_names=tuple(axes),
                                 devices=np.empty(tuple(axes.values())))


@pytest.mark.parametrize("mesh,seeds,want", [
    (None, False, 1),
    (_mesh(data=16, model=16), False, 16),
    (_mesh(pod=2, data=16, model=16), False, 32),
    # seeds over the client axes: each replicate's rows on one device
    (_mesh(data=16, model=16), True, 1),
    (_mesh(seed=2, pod=1, data=3), True, 3),
    (_mesh(seed=4, pod=1, data=1), True, 1),
])
def test_mesh_client_shards(mesh, seeds, want):
    """The devices a launcher's shardings split one replicate's client
    rows over, which it passes to the round builder as ``client_shards``."""
    assert mesh_client_shards(mesh, seeds=seeds) == want
