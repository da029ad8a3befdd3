"""Fault injection (core/faults.py + the engine's mask_compute/mask_upload
threading).

Guarantees under test:
  * graceful degradation — all-dropped rounds (upload_survival=0) leave
    every strategy in REGISTRY with a finite FLState and finite metrics,
    flat AND tree substrate, kernel on/off for the fedawe family.
  * parity — with mid-round dropout + sanitization live, the chunked
    executor still matches the host loop bit-for-bit per strategy, and
    the fused Pallas upload kernel matches the reference path.
  * sanitization — a client shipping non-finite updates is demoted to
    dropped in-round (counted in n_rejected) and can never poison the
    global; a tiny norm_cap rejects every update and the global freezes.
  * trace replay — a recorded [T, m] 0/1 trace drives the compute mask
    bit-exactly (row t mod T) through the host loop, the S-batched seeds
    executor, and the packed grid executor.
  * metrics contract — fault_cfg=None keeps the original 3-key metrics
    dict; a live FaultCfg adds exactly n_dropped and n_rejected.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (REGISTRY, AvailabilityCfg, FaultCfg, FLConfig,
                        init_fault_state, init_fl_state, make_chunk_fn,
                        make_grid_chunk_fn, make_round_fn,
                        make_seeds_chunk_fn, run_rounds, stack_seeds)
from repro.data import device_store, make_device_sampler

M, S, B, DIM = 6, 3, 4, 4


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


def _run(strategy, fault_cfg, *, flat, chunk, use_kernel=False, T=6, K=4,
         fault_state=None, nan_client=None, base_p=0.6):
    store, init_fn, sample_fn = _problem(nan_client=nan_client)
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                   lr_schedule=False, grad_clip=0.0, use_kernel=use_kernel,
                   flat_state=flat)
    av = AvailabilityCfg(kind="sine", gamma=0.3)
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), base_p),
                       fault_cfg=fault_cfg)
    state = init_fl_state(jax.random.PRNGKey(0), cfg, _tr0(),
                          fault=fault_state)
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


def _assert_same(s_host, s_chunk, h_host, h_chunk):
    for a, b in zip(jax.tree.leaves(s_host._replace(spec=None)),
                    jax.tree.leaves(s_chunk._replace(spec=None))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert len(h_host) == len(h_chunk)
    for rh, rc in zip(h_host, h_chunk):
        assert set(rh) == set(rc)
        for k in rh:
            np.testing.assert_allclose(rh[k], rc[k], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# graceful degradation: all-dropped rounds
# ---------------------------------------------------------------------------

ALL_DROPPED = FaultCfg(upload_survival=0.0, sanitize=True)


@pytest.mark.parametrize("flat", [False, True])
@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_all_dropped_rounds_stay_finite(strategy, flat):
    """upload_survival=0: every computed update is lost mid-round, every
    round.  Each strategy must degrade to a no-op aggregation — finite
    state, finite metrics, n_dropped == n_active."""
    state, hist = _run(strategy, ALL_DROPPED, flat=flat, chunk=False, T=4)
    _assert_finite_state(state)
    for r in hist:
        assert np.isfinite([r["loss"], r["mean_echo"]]).all()
        assert r["n_dropped"] == r["n_active"]
        assert r["n_rejected"] == 0.0


@pytest.mark.parametrize("flat", [False, True])
@pytest.mark.parametrize("strategy", ["fedawe", "fedawe_m"])
def test_all_dropped_rounds_stay_finite_kernel(strategy, flat):
    state, hist = _run(strategy, ALL_DROPPED, flat=flat, chunk=False, T=4,
                       use_kernel=True)
    _assert_finite_state(state)
    for r in hist:
        assert np.isfinite([r["loss"], r["mean_echo"]]).all()
        assert r["n_dropped"] == r["n_active"]


# ---------------------------------------------------------------------------
# parity under mid-round dropout: chunked == host, kernel == reference
# ---------------------------------------------------------------------------

MIDROUND = FaultCfg(upload_survival=0.7, sanitize=True)


@pytest.mark.parametrize("flat", [False, True])
@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_midround_chunked_matches_host_loop(strategy, flat):
    """T=6 at K=4 also exercises the shorter tail chunk (4 + 2); the
    4-way rng split and the upload draw ride the scan carry identically."""
    s_h, h_h = _run(strategy, MIDROUND, flat=flat, chunk=False)
    s_c, h_c = _run(strategy, MIDROUND, flat=flat, chunk=True)
    _assert_same(s_h, s_c, h_h, h_c)


@pytest.mark.parametrize("flat", [False, True])
@pytest.mark.parametrize("strategy", ["fedawe", "fedawe_m"])
def test_midround_kernel_matches_reference(strategy, flat):
    """The fused echo-aggregate kernel's upload variant (w = mask·upload
    computed in-kernel) must match the pure-jnp reference path."""
    s_r, h_r = _run(strategy, MIDROUND, flat=flat, chunk=False,
                    use_kernel=False)
    s_k, h_k = _run(strategy, MIDROUND, flat=flat, chunk=False,
                    use_kernel=True)
    _assert_same(s_r, s_k, h_r, h_k)


# ---------------------------------------------------------------------------
# sanitization
# ---------------------------------------------------------------------------

def _ones_trace(T):
    return np.ones((T, M), np.float32)


def test_sanitize_rejects_nonfinite_updates():
    """Client 0's shard is all-NaN, so its local update is non-finite
    every round; with an all-ones trace it is active every round and must
    be rejected every round — and the global stays finite regardless."""
    T = 4
    fc = FaultCfg(trace=True, sanitize=True)
    fs = init_fault_state(fc, trace=_ones_trace(T))
    state, hist = _run("fedawe", fc, flat=True, chunk=False, T=T,
                       fault_state=fs, nan_client=0)
    _assert_finite_state(state)
    for r in hist:
        assert r["n_active"] == M
        assert r["n_rejected"] == 1.0
        assert np.isfinite(r["loss"])


def test_sanitize_without_scrub_would_poison():
    """Negative control: the same NaN client with sanitization OFF poisons
    the aggregation — proving the scrub (not luck) keeps the test above
    finite."""
    T = 2
    fc = FaultCfg(trace=True, sanitize=False)
    fs = init_fault_state(fc, trace=_ones_trace(T))
    state, _ = _run("fedawe", fc, flat=True, chunk=False, T=T,
                    fault_state=fs, nan_client=0)
    assert not np.isfinite(np.asarray(state.global_tr)).all()


@pytest.mark.parametrize("flat", [False, True])
def test_norm_cap_rejects_everything_freezes_global(flat):
    """norm_cap ~ 0 classifies every non-zero update as exploded: all
    active clients are rejected, n_rejected == n_active, and the global
    never moves off its initialization."""
    T = 3
    fc = FaultCfg(sanitize=True, norm_cap=1e-8)
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy="fedawe",
                   lr_schedule=False, grad_clip=0.0, flat_state=flat)
    g0 = jax.tree.leaves(
        init_fl_state(jax.random.PRNGKey(0), cfg, _tr0()).global_tr)
    state, hist = _run("fedawe", fc, flat=flat, chunk=False, T=T)
    for a, b in zip(g0, jax.tree.leaves(state.global_tr)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for r in hist:
        assert r["n_rejected"] == r["n_active"]


def test_metrics_keys_contract():
    _, h_plain = _run("fedawe", None, flat=True, chunk=False, T=1)
    _, h_fault = _run("fedawe", MIDROUND, flat=True, chunk=False, T=1)
    assert set(h_plain[0]) == {"loss", "n_active", "mean_echo",
                               "n_computed", "t"}
    assert set(h_fault[0]) == {"loss", "n_active", "mean_echo", "n_computed",
                               "n_dropped", "n_rejected", "t"}


# ---------------------------------------------------------------------------
# trace replay: bit-exact through every executor
# ---------------------------------------------------------------------------

def _random_trace(T0, seed=7):
    return (np.random.default_rng(seed).random((T0, M)) < 0.5).astype(
        np.float32)


def test_trace_replay_bit_exact_host_loop():
    """n_active per round equals the trace row sum, rows consumed mod T0
    (T=7 over a 5-row trace wraps)."""
    T, T0 = 7, 5
    tr = _random_trace(T0)
    fc = FaultCfg(trace=True)
    fs = init_fault_state(fc, trace=tr)
    _, hist = _run("fedawe", fc, flat=True, chunk=False, T=T,
                   fault_state=fs)
    for t, r in enumerate(hist):
        assert r["n_active"] == tr[t % T0].sum()


def _seed_parts(strategy, fc, tr, n_seeds):
    store, init_fn, sample_fn = _problem()
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                   lr_schedule=False, grad_clip=0.0, flat_state=True)
    av = AvailabilityCfg(kind="sine", gamma=0.3)
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), 0.6),
                       fault_cfg=fc)
    states, sss, keys = [], [], []
    for j in range(n_seeds):
        fs = init_fault_state(fc, trace=tr)
        states.append(init_fl_state(jax.random.PRNGKey(j), cfg, _tr0(),
                                    fault=fs))
        dk = jax.random.PRNGKey(100 + j)
        sss.append(init_fn(store, dk))
        keys.append(dk)
    return (cfg, rf, sample_fn, store, stack_seeds(states),
            stack_seeds(sss), jnp.stack(keys), states, sss, keys)


def test_trace_replay_through_seeds_executor():
    """The [T0, m] trace rides the stacked scan carry: every seed
    replicate's compute mask follows the SAME recorded trace while its
    sgd/upload rng streams stay per-seed — n_active is [S, K] equal to
    the trace row sums, and each replicate's final state is bit-identical
    to its own single-seed chunked run."""
    K, S_SEEDS, T0 = 4, 2, 5
    tr = _random_trace(T0)
    fc = FaultCfg(trace=True, upload_survival=0.7, sanitize=True)
    (cfg, rf, sample_fn, store, states, sss, keys,
     states_1, sss_1, keys_1) = _seed_parts("fedawe", fc, tr, S_SEEDS)
    chunk = make_seeds_chunk_fn(cfg, rf, sample_fn, K, S_SEEDS,
                                donate=False)
    out_states, _, metrics = chunk(states, sss, store, keys)
    want = tr[:K].sum(axis=1)
    np.testing.assert_array_equal(
        np.asarray(metrics["n_active"]),
        np.broadcast_to(want, (S_SEEDS, K)))
    # per-seed parity vs the plain chunked executor
    single = make_chunk_fn(cfg, rf, sample_fn, K, donate=False)
    for j in range(S_SEEDS):
        s_j, _, m_j = single(states_1[j], sss_1[j], store, keys_1[j])
        for a, b in zip(
                jax.tree.leaves(s_j._replace(spec=None)),
                jax.tree.leaves(
                    jax.tree.map(lambda x: x[j],
                                 out_states._replace(spec=None)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(m_j["n_active"]), want)


def test_trace_replay_through_packed_executor():
    """Two grid cells (different strategies -> different subgraphs) packed
    into one dispatch both follow the recorded trace exactly."""
    K, S_SEEDS, T0 = 3, 2, 5
    tr = _random_trace(T0)
    fc = FaultCfg(trace=True)
    cells, states_t, sss_t, keys_t, stores = [], [], [], [], []
    for strategy in ("fedawe", "mifa"):
        (cfg, rf, sample_fn, store, states, sss, keys,
         *_rest) = _seed_parts(strategy, fc, tr, S_SEEDS)
        cells.append((rf, sample_fn))
        states_t.append(states)
        sss_t.append(sss)
        keys_t.append(keys)
        stores.append(store)
    packed = make_grid_chunk_fn(cells, K, S_SEEDS, donate=False)
    _, _, metrics_t = packed(tuple(states_t), tuple(sss_t), tuple(stores),
                             tuple(keys_t))
    want = np.broadcast_to(tr[:K].sum(axis=1), (S_SEEDS, K))
    for m in metrics_t:
        np.testing.assert_array_equal(np.asarray(m["n_active"]), want)


# ---------------------------------------------------------------------------
# blackout targeting
# ---------------------------------------------------------------------------

def test_blackout_zeroes_targeted_cluster():
    """Clients labeled cluster 0 go dark for blackout_len rounds from
    blackout_start, recurring every blackout_every — visible as exact
    zeros in their per-round availability via an all-ones base trace."""
    T = 8
    clusters = np.array([0, 0, 0, 1, 1, 1], np.int32)
    fc = FaultCfg(trace=True, blackout_start=2, blackout_len=2,
                  blackout_every=4, blackout_cluster=0)
    fs = init_fault_state(fc, trace=_ones_trace(T), clusters=clusters)
    _, hist = _run("fedawe", fc, flat=True, chunk=False, T=T,
                   fault_state=fs)
    dark = {2, 3, 6, 7}                  # start=2, len=2, recurring @ 4
    for t, r in enumerate(hist):
        assert r["n_active"] == (3.0 if t in dark else 6.0), (t, r)
