"""Semi-asynchronous rounds (core/staleness.py + the engine's pending
ring-buffer threading).

Guarantees under test:
  * bounded delay — no update waits more than tau_max rounds: slot ages
    never exceed tau_max and the delivered-update conservation law
    sum(n_active) == sum(n_stale) + pending(final) holds for det AND
    geom delays (busy gating means each client has at most one in-flight
    update).
  * cadence — stationary p=1 with det delay 1 alternates compute rounds
    and delivery rounds exactly: n_active = m,0,m,0,... and
    n_stale = 0,m,0,m,...
  * parity — with the ring buffer live, the chunked executor matches the
    host loop bit-for-bit for EVERY strategy in REGISTRY (fedar
    included), the fused upload kernel matches the reference path under
    discounted float delivery weights, the S-batched seeds executor
    matches per-seed single runs, and the packed grid executor follows
    the same cadence.
  * zero-cost off switch — StalenessCfg(tau_max=0) compiles the
    byte-identical synchronous round function: bit-exact states and
    identical metrics keys vs staleness_cfg=None.
  * composition — staleness composes with mid-round dropout and
    sanitization at DELIVERY time: a NaN update parked in the buffer is
    scrubbed when it arrives, never when it enters.
  * metrics contract — a live StalenessCfg adds exactly n_stale and
    mean_staleness; composing a FaultCfg adds n_dropped/n_rejected too.
  * FedAR — rectification weights are 1/(1+d) on the cached innovation;
    ages=None degrades to plain replacement memory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (REGISTRY, AvailabilityCfg, FaultCfg, FLConfig,
                        FlatSpec, StalenessCfg, init_fault_state,
                        init_fl_state, init_staleness_state, make_chunk_fn,
                        make_grid_chunk_fn, make_round_fn,
                        make_seeds_chunk_fn, run_rounds, stack_seeds)
from repro.core.staleness import pending_count, staircase_delay_trace
from repro.data import device_store, make_device_sampler

M, S, B, DIM = 6, 3, 4, 4
N_FLAT = DIM * DIM + 7                   # _tr0's flat substrate width

DET1 = StalenessCfg(tau_max=2, kind="det", delay=1)
DET2 = StalenessCfg(tau_max=3, kind="det", delay=2)
GEOM = StalenessCfg(tau_max=4, kind="geom", p_next=0.5)


def _problem(seed=0, sampling="uniform", nan_client=None):
    rng = np.random.default_rng(seed)
    n = 48
    x = rng.normal(size=(n, DIM)).astype(np.float32)
    y = rng.normal(size=(n, DIM)).astype(np.float32)
    idx = [np.arange(i, n, M) for i in range(M)]
    if nan_client is not None:
        x[idx[nan_client]] = np.nan      # every batch of that client is bad
    init_fn, sample_fn = make_device_sampler(M, S, B, mode=sampling)
    return device_store(dict(x=x, y=y), idx), init_fn, sample_fn


def _loss_fn(tr, frozen, batch, rng):
    return (0.5 * jnp.mean((batch["x"] @ tr["w"] - batch["y"]) ** 2)
            + jnp.sum(tr["b"] ** 2))


def _tr0():
    return {"w": jnp.ones((DIM, DIM)) * 0.1, "b": jnp.zeros((7,))}


def _stale_state(stcfg, T=16):
    dtrace = None
    if stcfg is not None and stcfg.kind == "trace":
        dtrace = staircase_delay_trace(jax.random.PRNGKey(9), M, T)
    return (init_staleness_state(stcfg, N_FLAT, M, dtrace=dtrace)
            if stcfg is not None and stcfg.needs_state else None)


def _run(strategy, stcfg, *, chunk, fault_cfg=None, fault_state=None,
         use_kernel=False, T=6, K=4, nan_client=None, base_p=0.6,
         kind="sine"):
    store, init_fn, sample_fn = _problem(nan_client=nan_client)
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                   lr_schedule=False, grad_clip=0.0, use_kernel=use_kernel,
                   flat_state=True)
    av = AvailabilityCfg(kind=kind, gamma=0.3)
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), base_p),
                       fault_cfg=fault_cfg, staleness_cfg=stcfg)
    state = init_fl_state(jax.random.PRNGKey(0), cfg, _tr0(),
                          fault=fault_state, stale=_stale_state(stcfg, T))
    data_key = jax.random.PRNGKey(42)
    kw = dict(sample_fn=sample_fn, store=store, data_key=data_key,
              sampler_state=init_fn(store, data_key))
    if chunk:
        return run_rounds(state, rf, None, T, chunk_rounds=K, **kw)
    return run_rounds(state, rf, None, T, **kw)


def _assert_finite_state(state):
    for leaf in jax.tree.leaves(state._replace(spec=None, rng=None)):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            assert np.isfinite(arr).all()


def _assert_same(s_host, s_chunk, h_host, h_chunk, exact=False):
    for a, b in zip(jax.tree.leaves(s_host._replace(spec=None)),
                    jax.tree.leaves(s_chunk._replace(spec=None))):
        if exact:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    assert len(h_host) == len(h_chunk)
    for rh, rc in zip(h_host, h_chunk):
        assert set(rh) == set(rc)
        for k in rh:
            np.testing.assert_allclose(rh[k], rc[k], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# bounded delay: conservation + age bound + cadence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stcfg", [DET1, DET2, GEOM],
                         ids=["det1", "det2", "geom"])
def test_bounded_delay_conservation(stcfg):
    """Every computed update is delivered exactly once within tau_max
    rounds (or still pending at the horizon): sum over rounds of
    n_active == sum of n_stale + pending(final buffer), and no parked
    slot ever records an age beyond tau_max."""
    T = 10
    state, hist = _run("fedawe", stcfg, chunk=False, T=T)
    _assert_finite_state(state)
    n_active = sum(r["n_active"] for r in hist)
    n_stale = sum(r["n_stale"] for r in hist)
    assert n_active == n_stale + float(pending_count(state.stale)), \
        (n_active, n_stale, np.asarray(state.stale["ages"]))
    assert float(jnp.max(state.stale["ages"])) <= stcfg.tau_max
    for r in hist:
        assert r["mean_staleness"] <= stcfg.tau_max


def test_det_delay_cadence():
    """Stationary p=1, det delay 1: everyone computes at t, is busy at
    t+1 while their upload arrives — n_active alternates m,0 and n_stale
    alternates 0,m, and every delivery carries staleness exactly 1."""
    _, hist = _run("fedawe", DET1, chunk=False, T=6, base_p=1.0,
                   kind="stationary")
    assert [r["n_active"] for r in hist] == [M, 0.0] * 3
    assert [r["n_stale"] for r in hist] == [0.0, M] * 3
    for r in hist[1::2]:
        assert r["mean_staleness"] == 1.0


def test_trace_delay_schedule_runs():
    """A replayed staircase delay trace drives per-client delays; the run
    stays finite and the conservation law still holds."""
    stcfg = StalenessCfg(tau_max=4, kind="trace")
    T = 12
    state, hist = _run("fedawe", stcfg, chunk=False, T=T)
    _assert_finite_state(state)
    n_active = sum(r["n_active"] for r in hist)
    n_stale = sum(r["n_stale"] for r in hist)
    assert n_active == n_stale + float(pending_count(state.stale))


# ---------------------------------------------------------------------------
# parity: chunked == host, kernel == reference, seeds/packed executors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_stale_chunked_matches_host_loop(strategy):
    """T=6 at K=4 also exercises the shorter tail chunk (4 + 2); the
    5-way rng split, the ring buffer, and the delay draws ride the scan
    carry identically for every strategy — fedar included."""
    s_h, h_h = _run(strategy, GEOM, chunk=False)
    s_c, h_c = _run(strategy, GEOM, chunk=True)
    _assert_same(s_h, s_c, h_h, h_c)


@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_stale_faults_chunked_matches_host_loop(strategy):
    """Staleness composed with mid-round dropout: the 5-key split order
    (k_up before k_delay) is pinned by chunked-vs-host parity."""
    fc = FaultCfg(upload_survival=0.7, sanitize=True)
    s_h, h_h = _run(strategy, DET2, chunk=False, fault_cfg=fc)
    s_c, h_c = _run(strategy, DET2, chunk=True, fault_cfg=fc)
    _assert_same(s_h, s_c, h_h, h_c)


@pytest.mark.parametrize("strategy", ["fedawe", "fedawe_m"])
def test_stale_kernel_matches_reference(strategy):
    """The fused echo-aggregate kernel consumes the DISCOUNTED float
    delivery weights (gamma**d) and must match the pure-jnp path."""
    stcfg = StalenessCfg(tau_max=3, kind="geom", p_next=0.5, gamma=0.7)
    s_r, h_r = _run(strategy, stcfg, chunk=False, use_kernel=False)
    s_k, h_k = _run(strategy, stcfg, chunk=False, use_kernel=True)
    _assert_same(s_r, s_k, h_r, h_k)


def _seed_parts(strategy, stcfg, n_seeds):
    store, init_fn, sample_fn = _problem()
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                   lr_schedule=False, grad_clip=0.0, flat_state=True)
    av = AvailabilityCfg(kind="sine", gamma=0.3)
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), 0.6),
                       staleness_cfg=stcfg)
    states, sss, keys = [], [], []
    for j in range(n_seeds):
        states.append(init_fl_state(jax.random.PRNGKey(j), cfg, _tr0(),
                                    stale=_stale_state(stcfg)))
        dk = jax.random.PRNGKey(100 + j)
        sss.append(init_fn(store, dk))
        keys.append(dk)
    return (cfg, rf, sample_fn, store, stack_seeds(states),
            stack_seeds(sss), jnp.stack(keys), states, sss, keys)


def test_stale_through_seeds_executor():
    """The [tau_max, m, N] ring buffer rides the STACKED seeds carry:
    each replicate's final state is bit-identical to its own single-seed
    chunked run (per-seed delay draws diverge through the state rng)."""
    K, S_SEEDS = 4, 2
    (cfg, rf, sample_fn, store, states, sss, keys,
     states_1, sss_1, keys_1) = _seed_parts("fedawe", GEOM, S_SEEDS)
    chunk = make_seeds_chunk_fn(cfg, rf, sample_fn, K, S_SEEDS,
                                donate=False)
    out_states, _, metrics = chunk(states, sss, store, keys)
    assert "n_stale" in metrics and metrics["n_stale"].shape == (S_SEEDS, K)
    single = make_chunk_fn(cfg, rf, sample_fn, K, donate=False)
    for j in range(S_SEEDS):
        s_j, _, m_j = single(states_1[j], sss_1[j], store, keys_1[j])
        for a, b in zip(
                jax.tree.leaves(s_j._replace(spec=None)),
                jax.tree.leaves(
                    jax.tree.map(lambda x: x[j],
                                 out_states._replace(spec=None)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(m_j["n_stale"]),
                                      np.asarray(metrics["n_stale"][j]))


def test_stale_through_packed_executor():
    """Two packed grid cells (different strategies -> different
    subgraphs) both run the semi-async round: under stationary p=1 det
    delay 1 each cell's n_active/n_stale follow the alternating
    cadence."""
    K, S_SEEDS = 4, 2
    cells, states_t, sss_t, keys_t, stores = [], [], [], [], []
    for strategy in ("fedawe", "mifa"):
        store, init_fn, sample_fn = _problem()
        cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                       lr_schedule=False, grad_clip=0.0, flat_state=True)
        av = AvailabilityCfg(kind="stationary")
        rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), 1.0),
                           staleness_cfg=DET1)
        states, sss, keys = [], [], []
        for j in range(S_SEEDS):
            states.append(init_fl_state(jax.random.PRNGKey(j), cfg, _tr0(),
                                        stale=_stale_state(DET1)))
            dk = jax.random.PRNGKey(100 + j)
            sss.append(init_fn(store, dk))
            keys.append(dk)
        cells.append((rf, sample_fn))
        states_t.append(stack_seeds(states))
        sss_t.append(stack_seeds(sss))
        keys_t.append(jnp.stack(keys))
        stores.append(store)
    packed = make_grid_chunk_fn(cells, K, S_SEEDS, donate=False)
    _, _, metrics_t = packed(tuple(states_t), tuple(sss_t), tuple(stores),
                             tuple(keys_t))
    want_active = np.broadcast_to([M, 0.0, M, 0.0], (S_SEEDS, K))
    want_stale = np.broadcast_to([0.0, M, 0.0, M], (S_SEEDS, K))
    for m in metrics_t:
        np.testing.assert_array_equal(np.asarray(m["n_active"]),
                                      want_active)
        np.testing.assert_array_equal(np.asarray(m["n_stale"]), want_stale)


# ---------------------------------------------------------------------------
# zero-cost off switch: tau_max=0 is the synchronous engine, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [False, True])
def test_tau_max_zero_bit_parity(chunk):
    """StalenessCfg(tau_max=0) must normalize away: same rng split count,
    same metrics keys, bit-identical state vs staleness_cfg=None through
    the host loop AND the chunked executor."""
    s_off, h_off = _run("fedawe", StalenessCfg(tau_max=0), chunk=chunk)
    s_none, h_none = _run("fedawe", None, chunk=chunk)
    _assert_same(s_none, s_off, h_none, h_off, exact=True)
    assert set(h_off[0]) == {"loss", "n_active", "mean_echo", "n_computed",
                             "t"}


def test_tau_max_zero_bit_parity_seeds():
    """tau_max=0 through the S-batched seeds executor: bit-identical to
    the staleness-free stacked run."""
    K, S_SEEDS = 3, 2
    outs = []
    for stcfg in (StalenessCfg(tau_max=0), None):
        (cfg, rf, sample_fn, store, states, sss, keys,
         *_rest) = _seed_parts("fedawe", stcfg, S_SEEDS)
        chunk = make_seeds_chunk_fn(cfg, rf, sample_fn, K, S_SEEDS,
                                    donate=False)
        outs.append(chunk(states, sss, store, keys))
    (st_a, _, m_a), (st_b, _, m_b) = outs
    for a, b in zip(jax.tree.leaves(st_a._replace(spec=None)),
                    jax.tree.leaves(st_b._replace(spec=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(m_a) == set(m_b)
    for k in m_a:
        np.testing.assert_array_equal(np.asarray(m_a[k]),
                                      np.asarray(m_b[k]))


# ---------------------------------------------------------------------------
# composition with faults: sanitize at delivery, not at entry
# ---------------------------------------------------------------------------

def test_sanitize_scrubs_stale_nan_at_delivery():
    """Client 0 ships NaN updates that PARK in the ring buffer for a
    round before delivery; sanitization runs at delivery time, so the
    global stays finite and the arrival is counted in n_rejected."""
    T = 6
    fc = FaultCfg(trace=True, sanitize=True)
    fs = init_fault_state(fc, trace=np.ones((T, M), np.float32))
    state, hist = _run("fedawe", DET1, chunk=False, T=T, fault_cfg=fc,
                       fault_state=fs, nan_client=0, base_p=1.0,
                       kind="stationary")
    # the ring buffer legitimately holds the raw NaN payload (freed slots
    # are never read again); everything the MODEL carries must be finite
    _assert_finite_state(state._replace(stale=None))
    # delivery rounds: all m arrive, exactly the NaN client is rejected
    for r in hist[1::2]:
        assert r["n_stale"] == M
        assert r["n_rejected"] == 1.0
        assert np.isfinite(r["loss"])


def test_metrics_keys_contract():
    _, h_stale = _run("fedawe", DET1, chunk=False, T=1)
    fc = FaultCfg(upload_survival=0.7, sanitize=True)
    _, h_both = _run("fedawe", DET1, chunk=False, T=1, fault_cfg=fc)
    assert set(h_stale[0]) == {"loss", "n_active", "mean_echo", "n_computed",
                               "n_stale", "mean_staleness", "t"}
    assert set(h_both[0]) == {"loss", "n_active", "mean_echo", "n_computed",
                              "n_stale", "mean_staleness", "n_dropped",
                              "n_rejected", "t"}


# ---------------------------------------------------------------------------
# FedAR rectification
# ---------------------------------------------------------------------------

def test_fedar_rectification_weights():
    """r = 1/(1+d): a fresh delivery (d=0) replaces the cached
    innovation outright; a d=1 delivery blends half-way; non-delivering
    clients keep their cache; the global moves by eta_g * mean(mem)."""
    strat = REGISTRY["fedar"]
    m, n = 4, 3
    g0 = jnp.zeros((n,))
    mem0 = jnp.ones((m, n)) * 2.0
    G = jnp.ones((m, n)) * 6.0
    mask = jnp.array([1.0, 1.0, 1.0, 0.0])
    ages = jnp.array([0.0, 1.0, 3.0, 0.0])
    new_g, _, _, extra = strat.aggregate_flat(
        global_flat=g0, clients_flat=jnp.zeros((m, n)),
        x_end=jnp.zeros((m, n)), G=G, mask=mask, t=jnp.int32(0),
        tau=jnp.zeros((m,), jnp.int32), probs=jnp.full((m,), 0.5),
        extra={"mem": mem0}, eta_g=1.0, ages=ages)
    want = np.array([6.0, 4.0, 3.0, 2.0])      # r = 1, 1/2, 1/4, (kept)
    np.testing.assert_allclose(np.asarray(extra["mem"][:, 0]), want)
    np.testing.assert_allclose(np.asarray(new_g),
                               -np.full((n,), want.mean()), rtol=1e-6)


def test_fedar_ages_none_is_plain_replacement():
    """Without ages the rectifier degrades to r=1: selected rows replace
    their cache with the raw innovation (MIFA-style memory)."""
    strat = REGISTRY["fedar"]
    m, n = 3, 2
    mem0 = jnp.ones((m, n))
    G = jnp.ones((m, n)) * 5.0
    mask = jnp.array([1.0, 0.0, 1.0])
    _, _, _, extra = strat.aggregate_flat(
        global_flat=jnp.zeros((n,)), clients_flat=jnp.zeros((m, n)),
        x_end=jnp.zeros((m, n)), G=G, mask=mask, t=jnp.int32(0),
        tau=jnp.zeros((m,), jnp.int32), probs=jnp.full((m,), 0.5),
        extra={"mem": mem0}, eta_g=1.0)
    np.testing.assert_allclose(np.asarray(extra["mem"]),
                               [[5.0, 5.0], [1.0, 1.0], [5.0, 5.0]])


def test_fedar_semi_async_run_converges_finite():
    """End-to-end fedar under geometric delays with a gamma discount:
    finite state and a moving global (the memory term is live)."""
    stcfg = StalenessCfg(tau_max=4, kind="geom", p_next=0.5, gamma=0.7)
    state, hist = _run("fedar", stcfg, chunk=True, T=8)
    _assert_finite_state(state)
    g0 = np.asarray(jax.tree.leaves(
        init_fl_state(jax.random.PRNGKey(0),
                      FLConfig(m=M, s=S, strategy="fedar",
                               flat_state=True), _tr0()).global_tr)[0])
    assert not np.array_equal(np.asarray(state.global_tr), g0)
