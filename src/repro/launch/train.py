"""FedAWE training launcher:

    python -m repro.launch.train --preset image --strategy fedawe \
        --dynamics sine --rounds 300

The same command runs on the CPU (JAX_PLATFORMS=cpu; the Pallas echo
kernel in interpret mode) and on one TPU chip (the kernel compiled with
Mosaic).  ``chip_smoke.py`` at the repo root drives it on the chip.
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import save_fl_state
from repro.core import (AvailabilityCfg, FLConfig, base_probs,
                        global_trainables, init_fl_state, make_round_fn,
                        run_rounds)
from repro.core.availability import base_probs_from_data
from repro.data import SAMPLING_MODES, FederatedDataset, \
    dirichlet_partition, make_device_sampler, make_image_classification, \
    make_lm_tokens
from repro.models import cnn
from repro.models.config import BlockCfg, ModelConfig
from repro.models import init_params, lm_loss


def build_image_task(args, rng):
    """Synthetic image-classification task.  Returns ``(params, loss_fn,
    ds, base_p, eval_fn, init_fn)`` — ``init_fn(key)`` re-initializes the
    model from any PRNG key (paper-style per-seed full replication:
    ``--replicate full`` draws seed j's template from
    ``init_fn(fold_in(model_rng, j))``)."""
    task = make_image_classification(seed=args.seed, n=args.n_samples,
                                     shape=(8, 8, 1))
    nprng = np.random.default_rng(args.seed)
    idx, nu = dirichlet_partition(nprng, task.labels, args.m,
                                  alpha=args.alpha, min_per_client=args.batch)
    ds = FederatedDataset(dict(images=task.images, labels=task.labels), idx,
                          seed=args.seed)
    # per-client label distributions ride along for the fault scenarios
    # (nu-correlated availability, cluster blackouts — core/faults.py)
    ds.nu = jnp.asarray(nu)
    base_p = base_probs_from_data(rng, jnp.asarray(nu))

    def init_fn(key):
        return cnn.init_cnn(key, in_shape=(8, 8, 1),
                            n_classes=task.n_classes)

    params = init_fn(jax.random.PRNGKey(args.seed))
    loss_fn = cnn.make_image_loss_fn(cnn.cnn_apply)

    def eval_fn(state):
        batch = ds.eval_batch(1024, seed=1)
        acc = cnn.accuracy(cnn.cnn_apply, global_trainables(state),
                           {k: jnp.asarray(v) for k, v in batch.items()})
        return {"eval_acc": float(acc)}

    return params, loss_fn, ds, base_p, eval_fn, init_fn


def build_lm_task(args, rng):
    lm = make_lm_tokens(seed=args.seed, n_seq=4096, seq_len=32, vocab=97)
    cfg = ModelConfig("fl-lm-tiny", 2, 64, 4, 2, 16, 128, lm.vocab,
                      pattern=(BlockCfg("attn"),), dtype="float32",
                      remat=False)
    labels = lm.tokens[:, 1:]
    tokens = lm.tokens[:, :-1]
    nprng = np.random.default_rng(args.seed)
    # partition sequences by their dominant token as a 'label'
    pseudo = tokens.mean(axis=1).astype(np.int64) % 10
    idx, nu = dirichlet_partition(nprng, pseudo, args.m, alpha=args.alpha,
                                  min_per_client=args.batch)
    ds = FederatedDataset(dict(tokens=tokens, labels=labels), idx,
                          seed=args.seed)
    ds.nu = jnp.asarray(nu)
    base_p = base_probs_from_data(rng, jnp.asarray(nu))

    def init_fn(key):
        return init_params(key, cfg)

    params = init_fn(jax.random.PRNGKey(args.seed))

    def loss_fn(tr, frozen, batch, key):
        b = dict(tokens=batch["tokens"], labels=batch["labels"],
                 mask=jnp.ones_like(batch["labels"], jnp.float32))
        return lm_loss(tr, cfg, b)

    def eval_fn(state):
        batch = ds.eval_batch(256, seed=1)
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        b["mask"] = jnp.ones_like(b["labels"], jnp.float32)
        return {"eval_loss": float(lm_loss(global_trainables(state), cfg, b))}

    return params, loss_fn, ds, base_p, eval_fn, init_fn


# resolution order for the scenario-overridable flags: explicit CLI value
# (even when it equals the default) > --scenario registry cell > default.
# Their argparse defaults are None sentinels so "passed the default value"
# and "not passed" are distinguishable.
_SCENARIO_FLAG_DEFAULTS = dict(strategy="fedawe", dynamics="stationary",
                               sampling="uniform", gamma=0.3, alpha=0.1,
                               eta_l=0.05, eta_g=1.0)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.train")
    ap.add_argument("--preset", default="image", choices=["image", "lm"])
    ap.add_argument("--strategy", default=None,
                    help="aggregation strategy (default: fedawe)")
    ap.add_argument("--dynamics", default=None,
                    choices=["stationary", "staircase", "sine",
                             "interleaved_sine", "markov"],
                    help="availability process (default: stationary)")
    ap.add_argument("--gamma", type=float, default=None,
                    help="sine-family amplitude (default: 0.3)")
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--m", type=int, default=32)
    ap.add_argument("--s", type=int, default=5)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--eta-l", type=float, default=None,
                    help="local lr (default: 0.05)")
    ap.add_argument("--eta-g", type=float, default=None,
                    help="global lr (default: 1.0)")
    ap.add_argument("--alpha", type=float, default=None,
                    help="Dirichlet heterogeneity (default: 0.1)")
    ap.add_argument("--n-samples", type=int, default=20000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas echo-aggregate (FedAWE family)")
    ap.add_argument("--flat-state", action="store_true",
                    help="flat [m, N] client-state substrate "
                         "(single-launch fused aggregation)")
    ap.add_argument("--chunk-rounds", type=int, default=0,
                    help="K>0: scan-chunked executor — K rounds per "
                         "dispatch, device-resident batch sampling, "
                         "donated FLState, eval/ckpt at chunk boundaries "
                         "(0 = host-loop single-seed, auto K=8 with "
                         "--seeds > 1)")
    ap.add_argument("--compile-cache", default="", metavar="DIR",
                    help="enable jax's persistent compilation cache in "
                         "DIR ('auto' = .jax_cache/ at the checkout root; "
                         "$JAX_COMPILATION_CACHE_DIR, when set, wins — see "
                         "launch/compilecache); warm re-runs skip XLA "
                         "compilation entirely")
    ap.add_argument("--sparse-cohort", type=int, default=0,
                    metavar="C_MAX",
                    help="O(cohort) rounds (core/cohort.py): gather the "
                         "round's active clients — capped at C_MAX, "
                         "overflow defers deterministically to later "
                         "rounds — into a [C_MAX, N] f32 working set, run "
                         "local updates and aggregation there, scatter "
                         "the touched rows back; the resident [m, N] "
                         "stack is never touched O(m*N) per round "
                         "(0 = dense rounds, the default; implies "
                         "--flat-state)")
    ap.add_argument("--resident-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="storage dtype of the resident [m, N] client "
                         "stack under --sparse-cohort: bfloat16 halves "
                         "residency; the cohort gather promotes rows to "
                         "f32, the scatter-back demote confines "
                         "non-finite rows (int8 is reserved — see "
                         "core/flatten.py)")
    ap.add_argument("--sampling", default=None,
                    choices=list(SAMPLING_MODES),
                    help="device-sampler mode (default: uniform): i.i.d. "
                         "uniform with replacement, or epoch-permutation "
                         "(every client visits each of its samples exactly "
                         "once per epoch; carried cursor, identical in "
                         "host and chunked executors)")
    ap.add_argument("--seeds", type=int, default=1,
                    help="S>1: run S seed replicates at once through the "
                         "vmapped multi-seed executor (one dispatch "
                         "advances every replicate one chunk; per-seed "
                         "results bit-identical to S independent runs "
                         "with rng/data keys fold_in(seed_key, j)); "
                         "reports mean±std over seeds")
    ap.add_argument("--replicate", default="shared",
                    choices=["shared", "full"],
                    help="multi-seed template mode (--seeds S>1): 'shared' "
                         "starts every replicate from one model init "
                         "(seeds vary only the stochastic draws; the "
                         "original executor behaviour), 'full' re-"
                         "initializes the model per seed from "
                         "fold_in(model_rng, j) — the paper's fully "
                         "independent replicates")
    ap.add_argument("--scenario", default=None,
                    help="named experiment-grid cell (launch/experiments "
                         "--list): supplies --strategy/--dynamics/"
                         "--sampling/--gamma/--alpha/--eta-l/--eta-g and "
                         "the availability knobs from the registry; any "
                         "of those flags you pass explicitly still wins, "
                         "even when passed its default value")
    ap.add_argument("--midround-drop", type=float, default=0.0,
                    help="P(a computed update fails to upload) per client "
                         "per round — mid-round dropout fault injection "
                         "(core/faults.py); only delivered updates "
                         "aggregate")
    ap.add_argument("--sanitize", action="store_true",
                    help="demote clients with non-finite local updates to "
                         "dropped for the round instead of poisoning the "
                         "aggregate (adds n_dropped/n_rejected metrics)")
    ap.add_argument("--norm-cap", type=float, default=0.0,
                    help="with --sanitize: also reject updates with "
                         "||G_i|| above this cap (0 = non-finite only)")
    ap.add_argument("--stale-max", type=int, default=None,
                    help="semi-async rounds (core/staleness.py): bound "
                         "straggler upload delay by tau_max rounds; a "
                         "delayed update parks in the pending ring buffer "
                         "and aggregates on arrival (0 = synchronous, the "
                         "default; implies --flat-state)")
    ap.add_argument("--stale-kind", default=None,
                    choices=["det", "geom", "trace"],
                    help="delay dynamics (default: det): det = every "
                         "straggler takes --stale-delay rounds, geom = "
                         "geometric arrival with --stale-p, trace = "
                         "replayed staircase per-client delay schedule")
    ap.add_argument("--stale-delay", type=int, default=None,
                    help="det delay in rounds (default: 1)")
    ap.add_argument("--stale-p", type=float, default=None,
                    help="geom per-round arrival probability (default: 0.5)")
    ap.add_argument("--stale-gamma", type=float, default=None,
                    help="staleness delivery discount base: an update "
                         "arriving d rounds late aggregates with weight "
                         "gamma**d (default: 1.0 = undiscounted)")
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--out", default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="overwrite --ckpt every N rounds (chunk-aligned; "
                         "multi-seed runs checkpoint seed 0 at the end)")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="RESUMABLE run artifact prefix "
                         "(checkpointing.save_run_state writes PATH.npz + "
                         "PATH.json holding the FLState AND the carried "
                         "SamplerState): every --ckpt-every rounds the run "
                         "overwrites the artifact, and when it already "
                         "exists the run restores it and continues to "
                         "--rounds instead of starting over; forces the "
                         "device-sampler path (the sampler carry is part "
                         "of the artifact)")
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.compile_cache:
        from repro.launch import compilecache
        print(f"compilation cache: {compilecache.enable(args.compile_cache)}",
              flush=True)

    scenario = None
    if args.scenario:
        from repro.launch.experiments import get_scenario
        scenario = get_scenario(args.scenario)
        args.flat_state = args.flat_state or scenario.flat_state
    # None sentinel = flag not passed: fill from the scenario cell (when
    # given) else the documented default — an explicitly-passed flag wins
    # over the scenario even when it equals the default (a sweep point at
    # --eta-l 0.05 must not be silently flattened to the cell's eta_l)
    for attr, fallback in _SCENARIO_FLAG_DEFAULTS.items():
        if getattr(args, attr) is None:
            if scenario is not None:
                sc_attr = "kind" if attr == "dynamics" else attr
                setattr(args, attr, getattr(scenario, sc_attr))
            else:
                setattr(args, attr, fallback)

    # semi-async knobs: the scenario cell's staleness config, with the
    # explicit CLI stale flags composed on top (CLI wins where passed)
    from repro.core import staleness as stalemod
    stale_cfg = scenario.staleness() if scenario else None
    if any(v is not None for v in (args.stale_max, args.stale_kind,
                                   args.stale_delay, args.stale_p,
                                   args.stale_gamma)):
        import dataclasses
        s0 = stale_cfg or stalemod.StalenessCfg()
        stale_cfg = dataclasses.replace(
            s0,
            tau_max=s0.tau_max if args.stale_max is None else args.stale_max,
            kind=s0.kind if args.stale_kind is None else args.stale_kind,
            delay=s0.delay if args.stale_delay is None else args.stale_delay,
            p_next=s0.p_next if args.stale_p is None else args.stale_p,
            gamma=s0.gamma if args.stale_gamma is None else args.stale_gamma)
    if stale_cfg is not None and stale_cfg.tau_max == 0:
        stale_cfg = None
    # the pending-update ring buffer and the cohort gather/scatter both
    # ride the flat [m, N] substrate
    args.flat_state = (args.flat_state or stale_cfg is not None
                       or args.sparse_cohort > 0)

    rng = jax.random.PRNGKey(args.seed)
    build = build_image_task if args.preset == "image" else build_lm_task
    params, loss_fn, ds, base_p, eval_fn, init_fn = build(args, rng)

    fl = FLConfig(m=args.m, s=args.s, eta_l=args.eta_l, eta_g=args.eta_g,
                  strategy=args.strategy, use_kernel=args.use_kernel,
                  flat_state=args.flat_state,
                  sparse_cohort=args.sparse_cohort,
                  resident_dtype=args.resident_dtype)
    if scenario:
        import dataclasses
        # registry availability knobs, with any explicit CLI winner on top
        av = dataclasses.replace(scenario.availability(),
                                 kind=args.dynamics, gamma=args.gamma)
    else:
        av = AvailabilityCfg(kind=args.dynamics, gamma=args.gamma)

    # fault injection: the scenario cell's fault knobs, with the explicit
    # CLI fault flags composed on top (CLI wins where passed)
    from repro.core import faults
    fault_cfg = scenario.fault() if scenario else None
    if args.midround_drop or args.sanitize or args.norm_cap:
        import dataclasses
        fc0 = fault_cfg or faults.FaultCfg()
        fault_cfg = dataclasses.replace(
            fc0,
            upload_survival=(1.0 - args.midround_drop if args.midround_drop
                             else fc0.upload_survival),
            sanitize=fc0.sanitize or args.sanitize or args.norm_cap > 0,
            norm_cap=args.norm_cap or fc0.norm_cap)
    fault_state = None
    if fault_cfg is not None and fault_cfg.needs_state:
        trace = (faults.diurnal_trace(jax.random.PRNGKey(args.seed + 2),
                                      base_p, args.rounds)
                 if fault_cfg.trace else None)
        clusters = (faults.clusters_from_nu(ds.nu)
                    if fault_cfg.blackout_len > 0 else None)
        fault_state = faults.init_fault_state(fault_cfg, trace=trace,
                                              clusters=clusters)
    stale_state = None
    if stale_cfg is not None and stale_cfg.needs_state:
        from repro.core import FlatSpec
        dtrace = None
        if stale_cfg.kind == "trace":
            dtrace = stalemod.staircase_delay_trace(
                jax.random.PRNGKey(args.seed + 3), args.m, args.rounds)
        stale_state = stalemod.init_staleness_state(
            stale_cfg, FlatSpec.from_tree(params).size, args.m,
            dtrace=dtrace)
    round_fn = make_round_fn(fl, loss_fn, {}, av, base_p,
                             fault_cfg=fault_cfg, staleness_cfg=stale_cfg)

    if args.seeds > 1:
        return _main_multi_seed(args, fl, round_fn, params, ds, eval_fn,
                                rng, init_fn, fault_state, stale_state)
    state = init_fl_state(rng, fl, params, fault=fault_state,
                          stale=stale_state)

    ckpt_fn = None
    if args.ckpt and args.ckpt_every:
        def ckpt_fn(st, t):
            save_fl_state(args.ckpt, st, round_t=t)

    if args.chunk_rounds or args.sampling == "epoch" or args.resume \
            or args.sparse_cohort:
        # device sampler (always for the chunked executor; also for the
        # host loop under epoch sampling, whose carried cursor state lives
        # on device, for --resume, whose artifact carries the sampler, and
        # for --sparse-cohort, whose round gathers the cohort's batches
        # from emitted column draws): the dataset is resident and the
        # SamplerState is threaded through whichever executor runs
        store = ds.device_store()
        init_sampler_fn, sample_fn = make_device_sampler(
            args.m, args.s, args.batch, mode=args.sampling,
            min_count=min(len(ix) for ix in ds.client_indices),
            emit="cols" if args.sparse_cohort else "batches")
        data_key = jax.random.PRNGKey(args.seed + 1)
        sampler_state = init_sampler_fn(store, data_key)
        rounds_left = args.rounds
        if args.resume:
            from repro.checkpointing import restore_run_state, save_run_state
            # save_pytree writes PATH.npz + PATH.json — --resume is the
            # artifact PREFIX, so probe the manifest, not the bare path
            if os.path.exists(args.resume + ".json"):
                state, sampler_state = restore_run_state(
                    args.resume, state, sampler_state)
                done = int(state.t)
                rounds_left = max(args.rounds - done, 0)
                print(f"resumed {args.resume} at round {done}; "
                      f"{rounds_left} to go")
            if args.ckpt_every:
                # 3-arg hook: engine._call_ckpt hands it the CARRIED
                # sampler state, making the artifact resumable
                def ckpt_fn(st, t, ss):
                    save_run_state(args.resume, st, ss, round_t=t)
        state, hist = run_rounds(
            state, round_fn, None, rounds_left,
            chunk_rounds=args.chunk_rounds, sample_fn=sample_fn,
            store=store, data_key=data_key, sampler_state=sampler_state,
            log_every=max(1, rounds_left // 10),
            eval_fn=eval_fn, eval_every=args.eval_every,
            ckpt_fn=ckpt_fn, ckpt_every=args.ckpt_every)
    else:
        def batch_fn(t):
            return {k: jnp.asarray(v)
                    for k, v in ds.round_batches(t, args.s,
                                                 args.batch).items()}

        state, hist = run_rounds(state, round_fn, batch_fn, args.rounds,
                                 log_every=max(1, args.rounds // 10),
                                 eval_fn=eval_fn, eval_every=args.eval_every,
                                 ckpt_fn=ckpt_fn, ckpt_every=args.ckpt_every)
    final = eval_fn(state)
    print("final:", final)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(args=vars(args), final=final, history=hist), f)
    if args.ckpt:
        save_fl_state(args.ckpt, state)
    return final


def _main_multi_seed(args, fl, round_fn, params, ds, eval_fn, rng, init_fn,
                     fault_state=None, stale_state=None):
    """``--seeds S > 1``: drive the vmapped multi-seed executor.

    Always chunked (``--chunk-rounds`` or K=8): one dispatch advances all
    S replicates one chunk.  Replicate ``j`` uses ``fold_in(rng, j)`` /
    ``fold_in(data_key, j)`` — bit-identical to an independent run with
    those keys.  ``--replicate full`` additionally re-initializes the
    MODEL per seed (template ``init_fn(fold_in(rng, j))``, the paper's
    fully independent replicates); the default ``shared`` keeps one init
    template for every seed (bit-compatible with the original executor).
    Reports per-metric mean±std over seeds; ``--out`` records the
    aggregate curves plus every per-seed history; ``--ckpt`` saves seed
    0's final state.
    """
    from repro.core import index_seed
    from repro.launch import analysis
    from repro.launch.experiments import run_multi_seed

    states, hists, finals = run_multi_seed(
        fl, round_fn, params, ds, sampling=args.sampling, batch=args.batch,
        seeds=args.seeds, rounds=args.rounds,
        # the CLI's 0 is the documented auto sentinel; the driver itself
        # now REJECTS chunk_rounds <= 0 instead of silently assuming 8
        chunk_rounds=args.chunk_rounds or 8, rng=rng,
        data_key=jax.random.PRNGKey(args.seed + 1), eval_fn=eval_fn,
        eval_every=args.eval_every, log_every=max(1, args.rounds // 10),
        template_fn=init_fn if args.replicate == "full" else None,
        fault=fault_state, stale=stale_state)
    final = analysis.seed_summary(finals)
    print("final (mean±std over seeds):", final)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(args=vars(args), final=final,
                           curves=analysis.aggregate_seed_histories(hists),
                           history_per_seed=hists), f)
    if args.ckpt:
        save_fl_state(args.ckpt, index_seed(states, 0))
    return final


if __name__ == "__main__":
    main()
