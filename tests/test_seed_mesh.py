"""The seed axis as a REAL executor dimension: the ('seed','pod','data')
mesh (launch/mesh.make_seed_mesh), seed_pspecs threaded through the LIVE
``make_seeds_chunk_fn`` jit (launch/experiments.seed_chunk_shardings /
build_seed_executor), per-seed template replication modes, and the packed
grid executor (engine.make_grid_chunk_fn).

The acceptance guarantee under test: the S-batched executor UNDER THE SEED
MESH is bit-identical to S independent single-seed chunked runs in BOTH
template modes (shared template and per-seed full re-init), including a
``T % K`` tail chunk.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core import (AvailabilityCfg, FLConfig, index_seed,
                        init_fl_state, make_grid_chunk_fn, make_round_fn,
                        make_seeds_chunk_fn, run_rounds)
from repro.data import device_store, make_device_sampler
from repro.launch.experiments import (build_seed_batch, build_seed_executor,
                                      run_seed_rounds)
from repro.launch.mesh import make_seed_mesh, seed_mesh_shape

# runtime rails (conftest.strict_rails): no implicit host<->device
# transfers, strict dtype promotion, tracer-leak checking
pytestmark = pytest.mark.strict_rails

M, S_, B, DIM = 6, 3, 4, 4
SEEDS = 4


def _problem(sampling="uniform"):
    rng = np.random.default_rng(0)
    n = 48
    arrays = dict(x=rng.normal(size=(n, DIM)).astype(np.float32),
                  y=rng.normal(size=(n, DIM)).astype(np.float32))
    idx = [np.arange(i, n, M) for i in range(M)]
    init_fn, sample_fn = make_device_sampler(M, S_, B, mode=sampling)
    return device_store(arrays, idx), init_fn, sample_fn


def _loss_fn(tr, frozen, batch, rng):
    return (0.5 * jnp.mean((batch["x"] @ tr["w"] - batch["y"]) ** 2)
            + jnp.sum(tr["b"] ** 2))


def _template_fn(key):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (DIM, DIM)) * 0.1,
            "b": jax.random.normal(k2, (7,)) * 0.01}


def _tr0():
    return {"w": jnp.ones((DIM, DIM)) * 0.1, "b": jnp.zeros((7,))}


def _cfg_rf(sampling, kind, strategy="fedawe"):
    store, init_fn, sample_fn = _problem(sampling)
    cfg = FLConfig(m=M, s=S_, eta_l=0.03, strategy=strategy,
                   lr_schedule=False, grad_clip=0.0, flat_state=True)
    av = AvailabilityCfg(kind=kind, gamma=0.3)
    rf = make_round_fn(cfg, _loss_fn, {}, av, jnp.full((M,), 0.6))
    return cfg, rf, store, init_fn, sample_fn


# ---------------------------------------------------------------------------
# mesh sizing
# ---------------------------------------------------------------------------

def test_seed_mesh_shape_auto_sizing():
    # full seed axis when it fits; data absorbs the rest
    assert seed_mesh_shape(4, 512, multi_pod=True) == (4, 2, 64)
    assert seed_mesh_shape(8, 512) == (8, 1, 64)
    # seed axis is a DIVISOR of S sized to maximize devices USED, not to
    # maximize itself: S=4 on 6 chips takes (2,1,3) (all 6), not (4,1,1)
    assert seed_mesh_shape(4, 6) == (2, 1, 3)
    assert seed_mesh_shape(4, 4, multi_pod=True) == (2, 2, 1)
    assert seed_mesh_shape(3, 4, multi_pod=True) == (1, 2, 2)
    assert seed_mesh_shape(6, 8, multi_pod=True) == (2, 2, 2)
    # degenerate single-device tier: everything size 1
    assert seed_mesh_shape(4, 1) == (1, 1, 1)
    # pod axis alone does not fit -> None (caller degrades to the
    # standard 2-/3-axis mesh)
    assert seed_mesh_shape(4, 1, multi_pod=True) is None
    assert seed_mesh_shape(1, 0) is None


def test_make_seed_mesh_on_this_host():
    """On the 1-device test process the seed mesh degenerates to
    (1, 1, 1) but keeps the real axis names — placements stay valid."""
    mesh = make_seed_mesh(SEEDS)
    assert mesh.axis_names == ("seed", "pod", "data")
    assert mesh.devices.shape == (1, 1, 1)


def test_make_seed_mesh_degrades_to_standard_mesh():
    """When the pod axis alone exceeds the device count, make_seed_mesh
    returns the standard mesh — no 'seed' axis, and seed_axes_for then
    routes seeds over the client axes (the PR 4 placement)."""
    from repro.sharding import seed_axes_for

    with pytest.raises(RuntimeError):
        # multi-pod fallback needs >= 4 devices (test mesh) — on this
        # 1-device host even the fallback cannot fit, and it says so
        make_seed_mesh(SEEDS, multi_pod=True, test=True)
    mesh = make_seed_mesh(SEEDS)
    assert seed_axes_for(mesh) == "seed"
    # a seed-less mesh routes seeds over the client axes
    flat = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    assert seed_axes_for(flat) == ("data",)


# ---------------------------------------------------------------------------
# acceptance: mesh-sharded executor bit-parity, both template modes, tail
# ---------------------------------------------------------------------------

def _single_seed_runs(cfg, rf, store, init_fn, sample_fn, T, K, rng, dkey,
                      template_fn=None):
    """S independent single-seed chunked runs; replicate j uses
    fold_in(rng, j) / fold_in(dkey, j), and under full replication its
    template is template_fn(fold_in(rng, j)) — exactly the convention
    build_seed_batch stacks."""
    out = []
    for j in range(SEEDS):
        tmpl = (_tr0() if template_fn is None
                else template_fn(jax.random.fold_in(rng, j)))
        st = init_fl_state(jax.random.fold_in(rng, j), cfg, tmpl)
        dk = jax.random.fold_in(dkey, j)
        st, hist = run_rounds(st, rf, None, T, chunk_rounds=K,
                              sample_fn=sample_fn, store=store,
                              data_key=dk,
                              sampler_state=init_fn(store, dk))
        out.append((st, hist))
    return out


@pytest.mark.parametrize("template_mode,sampling,kind", [
    ("shared", "uniform", "sine"),
    ("shared", "epoch", "markov"),
    ("full", "uniform", "markov"),
    ("full", "epoch", "sine"),
])
def test_mesh_executor_bit_parity_both_template_modes(template_mode,
                                                      sampling, kind):
    """make_seeds_chunk_fn with the live ('seed','pod','data')-mesh
    shardings (+donation) in its jit == S independent single-seed chunked
    runs, to the bit — shared AND full-replication templates, T=5/K=2 so
    a tail chunk is exercised through the same sharded builder."""
    T, K = 5, 2
    tf = None if template_mode == "shared" else _template_fn
    cfg, rf, store, init_fn, sample_fn = _cfg_rf(sampling, kind)
    rng, dkey = jax.random.PRNGKey(0), jax.random.PRNGKey(42)
    singles = _single_seed_runs(cfg, rf, store, init_fn, sample_fn, T, K,
                                rng, dkey, template_fn=tf)

    mesh = make_seed_mesh(SEEDS)
    states, sss, dks = build_seed_batch(cfg, _tr0(), rng, dkey, init_fn,
                                        store, SEEDS, template_fn=tf)
    builder = build_seed_executor(cfg, rf, sample_fn, SEEDS, mesh=mesh,
                                  states=states, sampler_states=sss,
                                  store=store, data_keys=dks)
    states, hists = run_seed_rounds(
        states, builder(K), T, K, sampler_states=sss, store=store,
        data_keys=dks, n_seeds=SEEDS, make_tail_fn=builder)
    for j in range(SEEDS):
        st_j = index_seed(states, j)
        ref_st, ref_hist = singles[j]
        for a, b in zip(jax.tree.leaves(ref_st._replace(spec=None)),
                        jax.tree.leaves(st_j._replace(spec=None))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert len(ref_hist) == len(hists[j]) == T
        for rh, rb in zip(ref_hist, hists[j]):
            assert set(rh) == set(rb)
            for k in rh:
                assert rh[k] == rb[k], (j, k, rh, rb)


def test_mesh_executor_still_donates():
    """The live shardings must not cost the donation: inputs consumed."""
    cfg, rf, store, init_fn, sample_fn = _cfg_rf("epoch", "sine")
    rng, dkey = jax.random.PRNGKey(0), jax.random.PRNGKey(42)
    states, sss, dks = build_seed_batch(cfg, _tr0(), rng, dkey, init_fn,
                                        store, SEEDS)
    builder = build_seed_executor(cfg, rf, sample_fn, SEEDS,
                                  mesh=make_seed_mesh(SEEDS),
                                  states=states, sampler_states=sss,
                                  store=store, data_keys=dks)
    states2, sss2, _ = builder(2)(states, sss, store, dks)
    assert states.clients_tr.is_deleted()
    assert sss["perm"].is_deleted()
    assert not states2.clients_tr.is_deleted()
    assert not sss2["perm"].is_deleted()


def test_full_replication_differs_but_shares_nothing_spurious():
    """Full replication actually varies the init point per seed (distinct
    per-seed global trainables at t=0), while shared mode starts every
    replicate at the same point."""
    cfg, _, store, init_fn, _ = _cfg_rf("uniform", "sine")
    rng, dkey = jax.random.PRNGKey(0), jax.random.PRNGKey(42)
    st_shared, _, _ = build_seed_batch(cfg, _tr0(), rng, dkey, init_fn,
                                       store, SEEDS)
    st_full, _, _ = build_seed_batch(cfg, _tr0(), rng, dkey, init_fn,
                                     store, SEEDS,
                                     template_fn=_template_fn)
    g_sh = np.asarray(st_shared.global_tr)
    g_fu = np.asarray(st_full.global_tr)
    assert all((g_sh[0] == g_sh[j]).all() for j in range(SEEDS))
    for i in range(SEEDS):
        for j in range(i + 1, SEEDS):
            assert not (g_fu[i] == g_fu[j]).all(), (i, j)


# ---------------------------------------------------------------------------
# packed grid executor
# ---------------------------------------------------------------------------

def test_packed_grid_bit_identical_to_unpacked_cells():
    """make_grid_chunk_fn advancing two shape-compatible cells == each
    cell's own S-batched executor, to the bit (states and [S, K]
    metrics), with the packed states donated."""
    K = 2
    cells, carries = [], []
    for kind in ("sine", "markov"):
        cfg, rf, store, init_fn, sample_fn = _cfg_rf("epoch", kind)
        states, sss, dks = build_seed_batch(
            cfg, _tr0(), jax.random.PRNGKey(0), jax.random.PRNGKey(42),
            init_fn, store, SEEDS)
        cells.append((rf, sample_fn))
        carries.append(dict(states=states, sss=sss, store=store, dks=dks,
                            cfg=cfg, rf=rf, sample_fn=sample_fn,
                            init_fn=init_fn))
    packed = make_grid_chunk_fn(cells, K, SEEDS)
    st_t = tuple(c["states"] for c in carries)
    ss_t = tuple(c["sss"] for c in carries)
    store_t = tuple(c["store"] for c in carries)
    dk_t = tuple(c["dks"] for c in carries)
    out_st, out_ss, out_m = packed(st_t, ss_t, store_t, dk_t)
    assert st_t[0].clients_tr.is_deleted(), "packed states must donate"

    for ci, c in enumerate(carries):
        states, sss, dks = build_seed_batch(
            c["cfg"], _tr0(), jax.random.PRNGKey(0),
            jax.random.PRNGKey(42), c["init_fn"], c["store"], SEEDS)
        solo = make_seeds_chunk_fn(c["cfg"], c["rf"], c["sample_fn"], K,
                                   SEEDS, donate=False)
        ref_st, ref_ss, ref_m = solo(states, sss, c["store"], dks)
        for a, b in zip(jax.tree.leaves(ref_st._replace(spec=None)),
                        jax.tree.leaves(out_st[ci]._replace(spec=None))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ref_ss),
                        jax.tree.leaves(out_ss[ci])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for key in ref_m:
            np.testing.assert_array_equal(np.asarray(ref_m[key]),
                                          np.asarray(out_m[ci][key]))


def test_pack_cells_groups_by_shape_signature():
    """Cells whose state shapes differ (stateful MIFA memory vs stateless
    fedavg) land in different groups; same-shape cells share one.  With
    ``pad=True`` the shape split stops mattering: ``make_grid_chunk_fn``
    never required cells to share shapes, so the groups merge down to ONE
    dispatch stream per (seeds, K, rounds)."""
    from repro.launch.experiments import build_cell, get_scenario, \
        pack_cells

    kw = dict(seeds=2, rounds=4, chunk_rounds=2, m=6, s=2, batch=4,
              n_samples=600, preset="image", seed=0)
    cells = [build_cell(get_scenario(n), **kw)
             for n in ("fedawe/sine", "fedawe/markov", "mifa/sine")]
    groups = pack_cells(cells)
    sizes = sorted(len(g) for g in groups)
    assert sizes == [1, 2], [
        [c["sc"].name for c in g] for g in groups]
    merged = pack_cells(cells, pad=True)
    assert [len(g) for g in merged] == [3], \
        "pad=True must merge same-(S,K,T) cells into one stream"
    # same alpha everywhere -> same sampler cap -> nothing to pad
    assert not any(c.get("padded_cap") for c in cells)


# ---------------------------------------------------------------------------
# packed x seed-mesh composition + bucket padding
# ---------------------------------------------------------------------------

def test_packed_mesh_bit_parity_including_tail():
    """run_packed_group under the seed mesh == each cell's own
    mesh-sharded S-batched drive (build_seed_executor +
    place_seed_batch), to the bit — final states AND per-seed history
    records — with T=5/K=2 so the T % K tail goes through the packed
    builder too (the tail used to be rebuilt WITHOUT the caller's
    shardings, silently dropping the mesh placement for the last
    dispatch)."""
    from repro.launch.experiments import place_seed_batch, run_packed_group

    T, K = 5, 2
    mesh = make_seed_mesh(SEEDS)
    rng, dkey = jax.random.PRNGKey(0), jax.random.PRNGKey(42)

    def build(kind):
        cfg, rf, store, init_fn, sample_fn = _cfg_rf("uniform", kind)
        states, sss, dks = build_seed_batch(cfg, _tr0(), rng, dkey,
                                            init_fn, store, SEEDS)
        return dict(fl=cfg, round_fn=rf, sample_fn=sample_fn, store=store,
                    states=states, sampler_states=sss, data_keys=dks,
                    eval_fn=None, seeds=SEEDS, rounds=T, K=K)

    kinds = ("sine", "markov")
    refs = []
    for kind in kinds:
        c = build(kind)
        builder = build_seed_executor(
            c["fl"], c["round_fn"], c["sample_fn"], SEEDS, mesh=mesh,
            states=c["states"], sampler_states=c["sampler_states"],
            store=c["store"], data_keys=c["data_keys"])
        states, sss, store, dks = place_seed_batch(
            builder.in_shardings, c["states"], c["sampler_states"],
            c["store"], c["data_keys"])
        st, hists = run_seed_rounds(states, builder(K), T, K,
                                    sampler_states=sss, store=store,
                                    data_keys=dks, n_seeds=SEEDS,
                                    make_tail_fn=builder)
        refs.append((st, hists))

    states_t, hists_t = run_packed_group([build(k) for k in kinds],
                                         mesh=mesh)
    for ci in range(len(kinds)):
        ref_st, ref_h = refs[ci]
        for a, b in zip(jax.tree.leaves(ref_st._replace(spec=None)),
                        jax.tree.leaves(states_t[ci]._replace(spec=None))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert hists_t[ci] == ref_h


@pytest.mark.slow
def test_bucket_cap_padding_bit_parity():
    """Two alpha ablations of one cell (different Dirichlet partitions ->
    different sampler caps) bucket-pad into ONE packed stream whose
    per-cell records are IDENTICAL to their unpadded ``run_scenario``
    drives: cap padding never changes a draw (the sampler's picks are
    count-bounded and the gather never reads a padded column)."""
    import json

    from repro.launch.experiments import (build_cell, get_scenario,
                                          pack_cells, run_packed_grid,
                                          run_scenario)

    kw = dict(seeds=2, rounds=5, chunk_rounds=2, m=6, s=2, batch=4,
              n_samples=600, preset="image", seed=0)
    names = ("fedawe/sine", "fedawe/sine@iid")
    cells = [build_cell(get_scenario(n), **kw) for n in names]
    caps = [c["store"]["idx"].shape[1] for c in cells]
    assert caps[0] != caps[1], "ablation pair must differ in cap"
    groups = pack_cells(cells, pad=True)
    assert len(groups) == 1 and len(groups[0]) == 2
    assert sum(bool(c.get("padded_cap")) for c in cells) == 1

    refs = [run_scenario(get_scenario(n), **kw) for n in names]
    got = run_packed_grid(list(names), pad=True, **kw)
    assert json.dumps(got, default=str) == json.dumps(refs, default=str)


@pytest.mark.slow
def test_pad_m_parity_and_padded_rows_inert():
    """A client-axis-padded cell (``build_cell(pad_m=...)``) driven
    packed == the SAME padded config under the plain S-batched executor,
    to the bit — and its padded clients are provably inert: their
    participation clocks never tick and their Markov chains stay off.
    (Padding m changes the rng stream shapes, so the contract is parity
    with the padded config's own unpacked drive, not with the original
    m-client cell — see _pad_m_config.)"""
    from repro.launch.experiments import (build_cell, get_scenario,
                                          run_packed_group)

    kw = dict(seeds=2, rounds=4, chunk_rounds=2, m=6, s=2, batch=4,
              n_samples=600, preset="image", seed=0)
    PAD = 8
    cell = build_cell(get_scenario("fedawe/markov"), pad_m=PAD, **kw)
    assert cell["fl"].m == PAD
    assert cell["store"]["idx"].shape[0] == PAD

    ref = build_cell(get_scenario("fedawe/markov"), pad_m=PAD, **kw)
    chunk_fn = make_seeds_chunk_fn(ref["fl"], ref["round_fn"],
                                   ref["sample_fn"], 2, 2)
    ref_st, ref_h = run_seed_rounds(
        ref["states"], chunk_fn, 4, 2,
        sampler_states=ref["sampler_states"], store=ref["store"],
        data_keys=ref["data_keys"], n_seeds=2)

    states_t, hists_t = run_packed_group([cell])
    for a, b in zip(jax.tree.leaves(ref_st._replace(spec=None)),
                    jax.tree.leaves(states_t[0]._replace(spec=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert hists_t[0] == ref_h
    st = states_t[0]
    assert (np.asarray(st.tau)[:, 6:] == -1).all(), \
        "padded clients must never participate"
    assert (np.asarray(st.markov)[:, 6:] == 0.0).all(), \
        "padded Markov chains must start (and stay) off"
