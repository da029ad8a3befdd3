"""Smoke run of FedAWE training on a TPU: the quickest proof that the main
path still starts on the chip.

    python chip_smoke.py               # phases a, b, c on one chip
    python chip_smoke.py --four-chips  # seed mesh vs unsharded, four chips

Phases (one chip):

  a. the training CLI in-process, as users run it: ``repro.launch.train``
     with the fused echo kernel on the flat substrate, 16 rounds in chunks
     of 8; then the same with ``--seeds 4`` (the vmapped multi-seed
     executor).
  b. the paper's CNN (Table 6) at its real 32x32x3 input, N = 273,706
     trainable parameters, m = 100 clients, through ``make_round_fn``, the
     device sampler and the chunked executor.
  c. the same model as a sparse cohort: m = 4096 clients resident in
     bfloat16, at most 256 of them trained per round, on batches of 16.

For (b) and (c) the chunk program is compiled once, checked to hold the
Mosaic echo kernel (``tpu_custom_call``), and that executable runs the
rounds.  Each phase prints one line: shapes, rounds done, final loss (and
``eval_acc`` where the phase evaluates), wall seconds (smoke time, not a
benchmark number), the device's ``peak_bytes_in_use`` so far, and the
persistent compilation cache's hit/miss counters.  The cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` of the checkout.

``--four-chips`` runs only the seed-mesh path: the ``fedawe/sine`` grid cell
with 4 seeds on a (4, 1, 1) ``('seed', 'pod', 'data')`` mesh and the same
call unsharded, at phase (a)'s sizes without the echo kernel and with f32
matmuls, and compares every seed's final global parameters and every
per-round metric, failing above ``MESH_TOL``.

The last line of stdout is ``{"ok": true, "device": {...}}``.  The script
exits non-zero, before that line, when JAX finds no TPU, when any phase
fails, or when a loss is not finite.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROUNDS, CHUNK = 16, 8
M, S, BATCH = 100, 5, 32
CNN_SHAPE = (32, 32, 3)
SPARSE_M, SPARSE_C = 4096, 256
# The cohort trains SPARSE_C * batch images per local step.  At batch 32 the
# chunk program needs 18.56 GB of HBM (v5e compile): the CNN's 32-channel
# activations are padded 4x to the 128-lane tile.  Batch 16 needs ~14.2 GB.
SPARSE_BATCH = 16
# largest |difference| admitted between a seed on the mesh and the same seed
# unsharded, in final global parameters and in every per-round metric; the
# CPU tests hold the two bit-identical.  Both sides run with f32 matmuls
# ("highest" precision): under the TPU's default bf16 passes the two
# programs, tiled for different per-device batches, drifted 1.7e-4 apart in
# loss over 16 rounds (v5e x4), which hides what the comparison is for.
MESH_TOL = 1e-4


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _report(phase, t0, cache0, **fields):
    from repro.launch import compilecache
    c = compilecache.counters()
    fields.update(
        smoke_s=time.perf_counter() - t0,
        peak_bytes_in_use=_peak_bytes(),
        cache_hits=c["hits"] - cache0["hits"],
        cache_misses=c["misses"] - cache0["misses"])
    print(f"phase {phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _finite_losses(phase, losses):
    if not losses or not all(math.isfinite(v) for v in losses):
        raise SystemExit(f"phase {phase}: non-finite or missing loss {losses}")
    return losses[-1]


def phase_cli(seeds=1, *, m=M, s=S, rounds=ROUNDS, chunk=CHUNK,
              n_samples=20000):
    """Phase (a): ``repro.launch.train.main`` in-process."""
    from repro.launch import compilecache, train
    t0, cache0 = time.perf_counter(), compilecache.counters()
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "run.json")
        train.main(["--preset", "image", "--strategy", "fedawe",
                    "--dynamics", "sine", "--m", str(m), "--s", str(s),
                    "--flat-state", "--use-kernel", "--chunk-rounds",
                    str(chunk), "--rounds", str(rounds), "--n-samples",
                    str(n_samples), "--seeds", str(seeds), "--out", out])
        with open(out) as f:
            rec = json.load(f)
    hists = rec["history_per_seed"] if seeds > 1 else [rec["history"]]
    done = {len(h) for h in hists}
    if done != {rounds}:
        raise SystemExit(f"phase a: ran {done} rounds, expected {rounds}")
    loss = [_finite_losses("a", [r["loss"] for r in h]) for h in hists]
    acc = rec["final"]["eval_acc"]
    _report(f"a/seeds={seeds}", t0, cache0, m=m, s=s, input="8x8x1",
            seeds=seeds, rounds=rounds, final_loss=loss,
            eval_acc=acc["mean"] if seeds > 1 else acc)


def phase_cnn(phase, task, *, m, sparse_cohort=0, resident_dtype="float32",
              s=S, batch=BATCH, rounds=ROUNDS, chunk=CHUNK, seed=0,
              require_mosaic=True):
    """Phases (b) and (c): the CNN at ``task``'s input shape through
    ``make_round_fn``, the device sampler and the chunked executor."""
    from repro.core import (AvailabilityCfg, FLConfig, global_trainables,
                            init_fl_state, make_chunk_fn, make_round_fn,
                            run_rounds)
    from repro.core.availability import base_probs_from_data
    from repro.data import (FederatedDataset, dirichlet_partition,
                            make_device_sampler)
    from repro.launch import compilecache
    from repro.models import cnn

    t0, cache0 = time.perf_counter(), compilecache.counters()
    in_shape = task.images.shape[1:]
    idx, nu = dirichlet_partition(np.random.default_rng(seed), task.labels, m,
                                  alpha=0.1, min_per_client=batch)
    ds = FederatedDataset(dict(images=task.images, labels=task.labels), idx,
                          seed=seed)
    rng = jax.random.PRNGKey(seed)
    base_p = base_probs_from_data(rng, jnp.asarray(nu))
    params = cnn.init_cnn(rng, in_shape=in_shape, n_classes=task.n_classes)
    fl = FLConfig(m=m, s=s, strategy="fedawe", flat_state=True,
                  use_kernel=True, sparse_cohort=sparse_cohort,
                  resident_dtype=resident_dtype)
    round_fn = make_round_fn(fl, cnn.make_image_loss_fn(cnn.cnn_apply), {},
                             AvailabilityCfg(kind="sine", gamma=0.3), base_p)
    state = init_fl_state(rng, fl, params)
    n_params = int(state.global_tr.shape[0])
    store = ds.device_store()
    init_sampler, sample_fn = make_device_sampler(
        m, s, batch, min_count=min(len(ix) for ix in ds.client_indices),
        emit="cols" if sparse_cohort else "batches")
    data_key = jax.random.PRNGKey(seed + 1)
    sampler_state = init_sampler(store, data_key)

    # the executable that runs the rounds is the one checked for the kernel
    t_c = time.perf_counter()
    chunk_fn = make_chunk_fn(fl, round_fn, sample_fn, chunk).lower(
        state, sampler_state, store, data_key).compile()
    compile_s = time.perf_counter() - t_c
    if require_mosaic and "tpu_custom_call" not in chunk_fn.as_text():
        raise SystemExit(f"phase {phase}: the chunk program holds no "
                         "tpu_custom_call; the echo kernel did not compile "
                         "to Mosaic")
    state, hist = run_rounds(state, round_fn, None, rounds,
                             chunk_rounds=chunk, chunk_fn=chunk_fn,
                             sample_fn=sample_fn, store=store,
                             data_key=data_key, sampler_state=sampler_state)
    if len(hist) != rounds:
        raise SystemExit(f"phase {phase}: ran {len(hist)} rounds")
    loss = _finite_losses(phase, [r["loss"] for r in hist])
    batch_eval = {k: jnp.asarray(v) for k, v in ds.eval_batch(1024).items()}
    acc = float(cnn.accuracy(cnn.cnn_apply, global_trainables(state),
                             batch_eval))
    if not np.isfinite(np.asarray(state.global_tr)).all():
        raise SystemExit(f"phase {phase}: non-finite global parameters")
    extra = {}
    if sparse_cohort:
        extra = dict(c_max=sparse_cohort, resident=resident_dtype,
                     n_active_total=sum(r["n_active"] for r in hist),
                     n_deferred_total=sum(r["n_deferred"] for r in hist))
    _report(phase, t0, cache0, m=m, N=n_params,
            input="x".join(map(str, in_shape)), s=s, batch=batch, **extra,
            rounds=len(hist), final_loss=loss, eval_acc=acc,
            chunk_compile_s=compile_s, mosaic_kernel=require_mosaic)


def phase_seed_mesh(*, seeds=4, m=M, s=S, batch=BATCH, rounds=ROUNDS,
                    chunk=CHUNK, n_samples=20000, tol=MESH_TOL):
    """``--four-chips``: the ``fedawe/sine`` cell with ``seeds`` seeds on the
    seed mesh and unsharded, compared seed by seed.

    ``run_multi_seed`` on the cell built by ``_cell_task`` is what
    ``run_scenario`` runs; it is called here directly because the
    comparison needs the final states, which the cell record leaves out.
    The echo kernel stays off, as in ``run_scenario``'s default: XLA cannot
    partition a Mosaic kernel over the seed axis."""
    from repro.core import index_seed
    from repro.launch import compilecache, experiments
    from repro.launch.mesh import make_seed_mesh

    sc = experiments.get_scenario("fedawe/sine")
    results = {}
    for label, mesh in (("mesh", make_seed_mesh(seeds)), ("unsharded", None)):
        t0, cache0 = time.perf_counter(), compilecache.counters()
        fl, rf, params, ds, eval_fn, _, fault, stale = experiments._cell_task(
            sc, m=m, s=s, batch=batch, n_samples=n_samples, preset="image",
            seed=0, use_kernel=False, rounds=rounds)
        with jax.default_matmul_precision("highest"):
            states, hists, _ = experiments.run_multi_seed(
                fl, rf, params, ds, sampling=sc.sampling, batch=batch,
                seeds=seeds, rounds=rounds, chunk_rounds=chunk,
                rng=jax.random.PRNGKey(0), data_key=jax.random.PRNGKey(1),
                mesh=mesh, fault=fault, stale=stale)
        glob = np.stack([np.asarray(index_seed(states, j).global_tr)
                         for j in range(seeds)])
        for h in hists:
            _finite_losses(f"four-chips/{label}", [r["loss"] for r in h])
        results[label] = (glob, hists)
        shape = (None if mesh is None
                 else dict(zip(mesh.axis_names, mesh.devices.shape)))
        _report(f"four-chips/{label}", t0, cache0, mesh=shape, seeds=seeds,
                m=m, s=s, N=glob.shape[1], rounds=rounds,
                final_loss=[h[-1]["loss"] for h in hists])

    (g_mesh, h_mesh), (g_ref, h_ref) = results["mesh"], results["unsharded"]
    diffs = {"global_params": float(np.max(np.abs(g_mesh - g_ref)))}
    identical = bool(np.array_equal(g_mesh, g_ref))
    for key in h_ref[0][0]:
        a = np.array([[r[key] for r in h] for h in h_mesh])
        b = np.array([[r[key] for r in h] for h in h_ref])
        diffs[key] = float(np.max(np.abs(a - b)))
        identical = identical and bool(np.array_equal(a, b))
    print(f"four-chips compare: max_abs_diff={diffs} "
          f"bit_identical={identical} tol={tol}", flush=True)
    if not all(d <= tol for d in diffs.values()):
        raise SystemExit(f"seed mesh differs from unsharded beyond {tol}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the seed-mesh path on four chips and its "
                         "unsharded comparison")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); nothing was run")
    n_dev = len(jax.devices())
    if args.four_chips and n_dev != 4:
        raise SystemExit(f"chip_smoke --four-chips needs 4 TPU chips, "
                         f"JAX sees {n_dev}")

    from repro.launch import compilecache
    print(f"device: {dev.platform} {dev.device_kind} x{n_dev}; "
          f"jax {jax.__version__}; compile cache: {compilecache.enable()}",
          flush=True)
    if args.four_chips:
        phase_seed_mesh()
    else:
        phase_cli(seeds=1)
        phase_cli(seeds=4)
        # one data set serves both CNN phases: the sparse cohort's
        # partition needs a batch of samples for each of its 4096 clients
        from repro.data import make_image_classification
        task = make_image_classification(n=SPARSE_M * SPARSE_BATCH,
                                         shape=CNN_SHAPE)
        phase_cnn("b", task, m=M)
        phase_cnn("c", task, m=SPARSE_M, sparse_cohort=SPARSE_C,
                  resident_dtype="bfloat16", batch=SPARSE_BATCH)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))


if __name__ == "__main__":
    main()
