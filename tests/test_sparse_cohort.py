"""Sparse cohort substrate (core/cohort.py + the engine's O(cohort) round
path), pinned against the dense flat engine.

Guarantees under test:
  * dense parity, f32 residency — with ``sparse_cohort >= `` the active
    count, every strategy in REGISTRY evolves BIT-IDENTICALLY to the
    dense flat engine (global, client stack, tau, strategy extras and
    metrics), because every client outside the cohort carries exactly
    zero weight in the dense reductions.  Holds through the host loop,
    the chunked executor with a T % K tail, and composed with mid-round
    faults + sanitization and with semi-async (staleness) rounds.
  * tolerance parity, bf16 residency — the resident stacks stored in
    bf16 (gather-promote / accumulate-demote) track the dense f32 run to
    demote precision.
  * gather/scatter round-trip (property) — for random masks including
    empty and full cohorts, gather -> scatter is the identity on every
    untouched row and exact on touched rows; promote-demote is the
    identity for bf16 residency.
  * overflow — more actives than ``c_max`` defers the highest client
    indices deterministically BEFORE local work (``n_deferred`` metric;
    deferred tau never advances — no silent drop of a computed update).
  * residency validation — int8 is reserved (NotImplementedError), a
    sub-f32 residency without the sparse path is rejected, and the bf16
    demote confines non-finite values to the old resident row.
  * init at scale — ``init_fl_state`` + device-store/sampler init at
    m = 1e5 stays under a pinned live-bytes budget (the vectorized
    ``padded_client_index`` / ``contiguous_client_index`` path — no
    O(m) Python-loop intermediates).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import (REGISTRY, AvailabilityCfg, FaultCfg, FLConfig,
                        StalenessCfg, cohort_gather, cohort_scatter,
                        cohort_select, init_fl_state, init_staleness_state,
                        make_round_fn, resident_dtype, run_rounds)
from repro.data import (contiguous_client_index, device_store,
                        make_device_sampler)

M, S, B, DIM = 6, 3, 4, 4
N_FLAT = DIM * DIM + 7                   # _tr0's flat substrate width

STALE = StalenessCfg(tau_max=3, kind="det", delay=2)
FAULTS = FaultCfg(upload_survival=0.6, sanitize=True, norm_cap=50.0)


def _problem(seed=0, emit="batches", nan_client=None):
    rng = np.random.default_rng(seed)
    n = 48
    x = rng.normal(size=(n, DIM)).astype(np.float32)
    y = rng.normal(size=(n, DIM)).astype(np.float32)
    idx = [np.arange(i, n, M) for i in range(M)]
    if nan_client is not None:
        x[idx[nan_client]] = np.nan      # every batch of that client is bad
    init_fn, sample_fn = make_device_sampler(M, S, B, mode="uniform",
                                             emit=emit)
    return device_store(dict(x=x, y=y), idx), init_fn, sample_fn


def _loss_fn(tr, frozen, batch, rng):
    return (0.5 * jnp.mean((batch["x"] @ tr["w"] - batch["y"]) ** 2)
            + jnp.sum(tr["b"] ** 2))


def _tr0():
    return {"w": jnp.ones((DIM, DIM)) * 0.1, "b": jnp.zeros((7,))}


def _run(strategy, *, sparse=0, rdt="float32", chunk=0, T=6,
         fault_cfg=None, stcfg=None, nan_client=None, base_p=0.6):
    emit = "cols" if sparse else "batches"
    store, init_fn, sample_fn = _problem(emit=emit, nan_client=nan_client)
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy=strategy,
                   lr_schedule=False, grad_clip=0.0, flat_state=True,
                   sparse_cohort=sparse, resident_dtype=rdt)
    av = AvailabilityCfg(kind="sine", gamma=0.3)
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), base_p),
                       fault_cfg=fault_cfg, staleness_cfg=stcfg)
    stale = (init_staleness_state(stcfg, N_FLAT, M)
             if stcfg is not None and stcfg.needs_state else None)
    state = init_fl_state(jax.random.PRNGKey(0), cfg, _tr0(), stale=stale)
    data_key = jax.random.PRNGKey(42)
    kw = dict(sample_fn=sample_fn, store=store, data_key=data_key,
              sampler_state=init_fn(store, data_key))
    if chunk:
        return run_rounds(state, rf, None, T, chunk_rounds=chunk, **kw)
    return run_rounds(state, rf, None, T, **kw)


def _f32(x):
    return np.asarray(jnp.asarray(x).astype(jnp.float32))


def _assert_parity(dense, sparse_out, *, exact=True, rtol=0.0, atol=0.0):
    (sd, hd), (ss, hs) = dense, sparse_out

    def cmp(a, b, what):
        a, b = _f32(a), _f32(b)
        if exact:
            np.testing.assert_array_equal(a, b, err_msg=what)
        else:
            np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                                       err_msg=what)

    cmp(sd.global_tr, ss.global_tr, "global")
    assert (sd.clients_tr is None) == (ss.clients_tr is None)
    if sd.clients_tr is not None:
        cmp(sd.clients_tr, ss.clients_tr, "clients")
    np.testing.assert_array_equal(np.asarray(sd.tau), np.asarray(ss.tau))
    de, se = jax.tree.leaves(sd.extra), sd.extra
    del de, se
    # strategy extras: compare by key where the structures share one (the
    # cohort path may carry extra running sums alongside)
    if isinstance(sd.extra, dict) and isinstance(ss.extra, dict):
        for k in set(sd.extra) & set(ss.extra):
            cmp(sd.extra[k], ss.extra[k], f"extra[{k}]")
    elif not isinstance(ss.extra, dict):
        for a, b in zip(jax.tree.leaves(sd.extra), jax.tree.leaves(ss.extra)):
            cmp(a, b, "extra")
    assert len(hd) == len(hs)
    for rd, rs in zip(hd, hs):
        assert set(rs) - set(rd) == {"n_deferred"}
        assert rs["n_deferred"] == 0.0
        # path accounting, like n_deferred: the dense blocks count
        # ceil(n / block) * block rows, the cohort its cap
        assert rs["n_computed"] == M
        for k in set(rd) - {"n_computed"}:
            if exact:
                np.testing.assert_array_equal(rd[k], rs[k], err_msg=k)
            else:
                np.testing.assert_allclose(rd[k], rs[k], rtol=max(rtol, 1e-5),
                                           atol=max(atol, 1e-6), err_msg=k)


# ---------------------------------------------------------------------------
# dense parity: every strategy, f32 bit-exact / bf16 tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_dense_parity_f32(strategy):
    """c_max = m, f32 residency: the sparse path IS the dense computation
    (cohort reductions differ only by exact-zero terms)."""
    _assert_parity(_run(strategy), _run(strategy, sparse=M))


@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_dense_parity_bf16(strategy):
    """bf16 residency tracks the dense f32 run to demote precision."""
    _assert_parity(_run(strategy), _run(strategy, sparse=M, rdt="bfloat16"),
                   exact=False, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_dense_parity_chunked_tail(strategy):
    """Sparse chunked executor (T=7 rounds through K=4 chunks: one full
    chunk + a T % K tail) == dense host loop, bit-exact."""
    _assert_parity(_run(strategy, T=7),
                   _run(strategy, sparse=M, T=7, chunk=4))


# ---------------------------------------------------------------------------
# composition: faults and semi-async rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_dense_parity_under_faults(strategy):
    """Mid-round dropout + sanitization of a NaN client: the cohort fault
    draw is the full-[m] stream gathered at the cohort indices, so every
    client's fate — and n_dropped / n_rejected — matches dense exactly."""
    _assert_parity(
        _run(strategy, fault_cfg=FAULTS, nan_client=2),
        _run(strategy, sparse=M, fault_cfg=FAULTS, nan_client=2))


@pytest.mark.parametrize("strategy", sorted(REGISTRY))
def test_dense_parity_under_staleness(strategy):
    """Semi-async rounds: the sparse path scatters cohort results into
    dense lanes ahead of the ring buffer, bit-exact vs the dense engine."""
    _assert_parity(_run(strategy, stcfg=STALE, T=8),
                   _run(strategy, sparse=M, stcfg=STALE, T=8))


def test_dense_parity_faults_staleness_composed_chunked():
    """Everything at once: faults x staleness x sparse cohort through the
    chunked executor with a T % K tail."""
    _assert_parity(
        _run("fedawe", fault_cfg=FAULTS, stcfg=STALE, T=9),
        _run("fedawe", sparse=M, fault_cfg=FAULTS, stcfg=STALE, T=9,
             chunk=4))


def test_staleness_bf16_residency_finite():
    """bf16 residency composes with the dense-lane staleness path: the
    full-stack demote keeps the run finite and the carry in bf16."""
    st_, hist = _run("fedawe", sparse=M, rdt="bfloat16", stcfg=STALE, T=8)
    assert st_.clients_tr.dtype == jnp.bfloat16
    assert np.isfinite(_f32(st_.global_tr)).all()
    assert all(np.isfinite(r["loss"]) for r in hist)


# ---------------------------------------------------------------------------
# overflow: deterministic deferral, never a silent drop
# ---------------------------------------------------------------------------

def test_overflow_defers_deterministically():
    """p = 1 (all m active), c_max = 2: every round the two lowest client
    indices compute, everyone else is deferred and surfaced in
    n_deferred; deferred clients' tau never advances."""
    store, init_fn, sample_fn = _problem(emit="cols")
    cfg = FLConfig(m=M, s=S, eta_l=0.03, strategy="fedawe",
                   lr_schedule=False, grad_clip=0.0, flat_state=True,
                   sparse_cohort=2)
    av = AvailabilityCfg(kind="stationary")
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.ones((M,)))
    state = init_fl_state(jax.random.PRNGKey(0), cfg, _tr0())
    data_key = jax.random.PRNGKey(42)
    state, hist = run_rounds(state, rf, None, 5, sample_fn=sample_fn,
                             store=store, data_key=data_key,
                             sampler_state=init_fn(store, data_key))
    for r in hist:
        assert r["n_deferred"] == float(M - 2)
        assert r["n_active"] == 2.0
    tau = np.asarray(state.tau)
    assert (tau[:2] == 4).all()          # cohort clients participated at t=4
    assert (tau[2:] == -1).all()         # deferred: no silent participation


def test_metrics_contract():
    """The sparse path adds exactly ``n_deferred`` to the metrics dict."""
    _, hd = _run("fedawe", T=2)
    _, hs = _run("fedawe", sparse=M, T=2)
    assert set(hs[0]) - set(hd[0]) == {"n_deferred"}


# ---------------------------------------------------------------------------
# gather/scatter round-trip properties
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 1), min_size=1, max_size=24),
       st.integers(1, 30), st.sampled_from(["float32", "bfloat16"]),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=40, deadline=None)
def test_gather_scatter_roundtrip(bits, cap, rdt_name, seed):
    """gather -> scatter with the gathered rows is the identity on the
    whole resident stack (touched AND untouched rows), for empty, partial
    and full masks, at any cap, in f32 and bf16 residency."""
    m = len(bits)
    c_max = min(cap, m)
    rdt = resident_dtype(rdt_name)
    mask = jnp.asarray(bits, jnp.float32)
    resident = jax.random.normal(jax.random.PRNGKey(seed), (m, 5)) \
        .astype(rdt)
    idx, n_deferred = cohort_select(mask, c_max)
    rows = cohort_gather(resident, idx)
    assert rows.dtype == jnp.float32
    out = cohort_scatter(resident, idx, rows, jnp.take(mask, idx))
    assert out.dtype == rdt
    np.testing.assert_array_equal(_f32(out), _f32(resident))
    # overflow accounting: deferred == actives beyond the cap, never <0
    assert float(n_deferred) == max(0.0, float(sum(bits)) - c_max)


@given(st.lists(st.integers(0, 1), min_size=1, max_size=24),
       st.integers(1, 30), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=40, deadline=None)
def test_scatter_writes_only_the_written(bits, cap, seed):
    """Scattering NEW rows updates exactly the written slots (mask > 0 at
    a cohort index) and leaves every other row bit-identical."""
    m = len(bits)
    c_max = min(cap, m)
    mask = jnp.asarray(bits, jnp.float32)
    resident = jax.random.normal(jax.random.PRNGKey(seed), (m, 5))
    idx, _ = cohort_select(mask, c_max)
    mask_c = jnp.take(mask, idx)
    new_rows = cohort_gather(resident, idx) + 1.0
    out = cohort_scatter(resident, idx, new_rows, mask_c)
    written = np.zeros(m, bool)
    written[np.asarray(idx)[np.asarray(mask_c) > 0]] = True
    np.testing.assert_array_equal(np.asarray(out)[~written],
                                  np.asarray(resident)[~written])
    np.testing.assert_array_equal(np.asarray(out)[written],
                                  np.asarray(resident)[written] + 1.0)


def test_cohort_select_prefers_lowest_active_indices():
    mask = jnp.asarray([0, 1, 0, 1, 1, 1], jnp.float32)
    idx, n_def = cohort_select(mask, 3)
    np.testing.assert_array_equal(np.asarray(idx), [1, 3, 4])
    assert float(n_def) == 1.0           # client 5 deferred
    # under-full cohort: actives first, then lowest-index inactive padding
    idx2, n2 = cohort_select(mask, 5)
    np.testing.assert_array_equal(np.asarray(idx2), [1, 3, 4, 5, 0])
    assert float(n2) == 0.0


def test_bf16_demote_confines_nonfinite():
    """A NaN/inf working row demoted into a bf16 resident stack keeps the
    OLD resident row (the carry can never be poisoned persistently); f32
    residency propagates bit-exactly, NaN included (dense parity)."""
    resident16 = jnp.ones((3, 4), jnp.bfloat16)
    rows = jnp.stack([jnp.full((4,), jnp.nan),
                      jnp.full((4,), jnp.inf),
                      jnp.full((4,), 2.0)])
    out = cohort_scatter(resident16, jnp.arange(3), rows, jnp.ones((3,)))
    np.testing.assert_array_equal(_f32(out),
                                  [[1.0] * 4, [1.0] * 4, [2.0] * 4])
    resident32 = jnp.ones((3, 4), jnp.float32)
    out32 = cohort_scatter(resident32, jnp.arange(3), rows, jnp.ones((3,)))
    assert np.isnan(np.asarray(out32)[0]).all()
    assert np.isinf(np.asarray(out32)[1]).all()


# ---------------------------------------------------------------------------
# residency validation
# ---------------------------------------------------------------------------

def test_int8_residency_is_reserved():
    with pytest.raises(NotImplementedError, match="per-row quantization"):
        FLConfig(m=4, flat_state=True, sparse_cohort=2,
                 resident_dtype="int8")


def test_unknown_residency_rejected():
    with pytest.raises(ValueError, match="unknown resident_dtype"):
        resident_dtype("float16")


def test_sub_f32_residency_needs_sparse_path():
    with pytest.raises(ValueError, match="sparse_cohort"):
        FLConfig(m=4, flat_state=True, resident_dtype="bfloat16")


def test_sparse_needs_flat_substrate():
    with pytest.raises(AssertionError, match="flat"):
        FLConfig(m=4, sparse_cohort=2)


# ---------------------------------------------------------------------------
# init at scale: no O(m)-Python-loop intermediates, pinned live bytes
# ---------------------------------------------------------------------------

def test_huge_m_init_stays_under_live_bytes_budget():
    """m = 1e5 on the tiny model: device-store init (contiguous index, no
    per-client Python arrays), sampler init and ``init_fl_state`` together
    stay under a pinned live-bytes budget — the accounting that used to
    blow up through O(m·cap) host intermediates and per-leaf broadcasts."""
    m, n_per = 100_000, 2

    def live_bytes():
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.live_arrays())

    base = live_bytes()
    x = np.zeros((m * n_per, DIM), np.float32)
    y = np.zeros((m * n_per, DIM), np.float32)
    store = device_store(dict(x=x, y=y),
                         padded=contiguous_client_index(m, n_per))
    init_fn, sample_fn = make_device_sampler(m, 2, 1, mode="uniform",
                                             emit="cols")
    cfg = FLConfig(m=m, s=2, strategy="fedawe", flat_state=True,
                   sparse_cohort=64, resident_dtype="bfloat16")
    data_key = jax.random.PRNGKey(0)
    ss = init_fn(store, data_key)
    state = init_fl_state(jax.random.PRNGKey(1), cfg, _tr0())
    grown = live_bytes() - base
    # exact footprint: data 2*m*n_per*DIM*4 B, idx m*n_per*4 B, counts
    # m*4 B, bf16 client stack m*N*2 B, tau/markov m*(4+4) B, loc odds
    # and ends.  Budget = that + 25% slack; the pre-fix init held MULTIPLE
    # transient [m, cap]/[m, N] copies alive and busts it.
    expected = (2 * m * n_per * DIM * 4 + m * n_per * 4 + m * 4
                + m * N_FLAT * 2 + m * 8)
    assert grown < expected * 1.25, (grown, expected)
    del store, ss, state
