import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (test hook — still before any jax import, which locks the device count)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers AND compiles under the production sharding config.

  train_4k / prefill_32k  -> the FedAWE round / prefill forward
  decode_32k / long_500k  -> serve_step (1 new token, seq_len KV cache)

For each combination this prints/records compiled.memory_analysis() (fits)
and compiled.cost_analysis() (FLOPs/bytes for §Roofline) plus the collective
bytes parsed from the HLO. Results append incrementally to a JSON file so
interrupted sweeps resume.
"""
import argparse
import json
import math
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, supported_shapes
from repro.core import (AvailabilityCfg, FLConfig, init_fl_state,
                        make_chunk_fn, make_round_fn_with_frozen,
                        make_seeds_chunk_fn)
from repro.data import make_device_sampler
from repro.launch import analysis
from repro.launch.mesh import (make_production_mesh, make_seed_mesh,
                               make_test_mesh, n_chips)
from repro.models import (init_cache, init_params, lm_loss, merge_trainable,
                          split_trainable)
from repro.models.model import prefill, serve_step
from repro.sharding import (batch_pspecs, cache_pspecs, client_stack_pspecs,
                            flat_pspecs, mesh_client_shards, param_pspecs,
                            sampler_pspecs, seed_pspecs, serve_batch_pspecs)

I32 = jnp.int32
F32 = jnp.float32


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def fl_clients(mesh):
    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    return ax.get("pod", 1) * ax.get("data", 1)


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def train_input_specs(cfg, shape, m):
    b = max(1, shape.global_batch // m)
    s, L = cfg.local_steps, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    batch = dict(
        tokens=_sds((m, s, b, L), I32),
        labels=_sds((m, s, b, L), I32),
        mask=_sds((m, s, b, L), F32),
    )
    if cfg.frontend != "none":
        batch["embeds"] = _sds((m, s, b, cfg.frontend_len, cfg.d_model), dt)
    if cfg.enc_dec:
        batch["enc_embeds"] = _sds((m, s, b, cfg.enc_len, cfg.d_model), dt)
    return batch


def prefill_input_specs(cfg, shape):
    B, L = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    out = dict(tokens=_sds((B, L), I32))
    if cfg.frontend != "none":
        out["embeds"] = _sds((B, cfg.frontend_len, cfg.d_model), dt)
    if cfg.enc_dec:
        out["enc_embeds"] = _sds((B, cfg.enc_len, cfg.d_model), dt)
    return out


def decode_input_specs(cfg, shape):
    B = shape.global_batch
    return dict(tokens=_sds((B, 1), I32), pos=_sds((B,), I32))


# ---------------------------------------------------------------------------
# step builders: (jitted_fn, example_args) per shape kind
# ---------------------------------------------------------------------------

def _apply_cfg_variant(cfg, variant):
    """Config-level §Perf knobs encoded in the variant string."""
    if "dots_remat" in variant:
        cfg = cfg.replace(remat_policy="dots")
    if "moe_dshard" in variant:
        os.environ["REPRO_MOE_CONSTRAIN"] = "D"
    elif "moe_hint" in variant:
        os.environ["REPRO_MOE_CONSTRAIN"] = "1"
    else:
        os.environ.pop("REPRO_MOE_CONSTRAIN", None)
    return cfg


def build_train_step(cfg, shape, mesh, multi_pod, variant="baseline"):
    # dp_client:  replicate block weights, within-client batch over 'model'
    # zero_client: keep TP-sharded weight STORAGE but batch over 'model' —
    #              XLA then gathers weights per layer (ZeRO/FSDP pattern)
    mode = "dp" if "dp_client" in variant else "tp"
    batch_mode = "dp" if ("dp_client" in variant or "zero_client" in variant) \
        else "tp"
    m = fl_clients(mesh)
    fl = FLConfig(m=m, s=cfg.local_steps, eta_l=0.01, eta_g=1.0,
                  strategy="fedawe", lr_schedule=False, grad_clip=0.0)
    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    trainable_sds, frozen_sds = split_trainable(params_sds, cfg)

    def loss_fn(tr, fz, batch, rng):
        return lm_loss(merge_trainable(tr, fz, cfg), cfg, batch)

    av = AvailabilityCfg(kind="sine", gamma=0.3, period=20)
    base_p = jnp.full((m,), 0.5, F32)
    round_fn = make_round_fn_with_frozen(fl, loss_fn, av, base_p)

    state_sds = jax.eval_shape(
        lambda tr: init_fl_state(jax.random.PRNGKey(0), fl, tr),
        trainable_sds)
    batch_sds = train_input_specs(cfg, shape, m)

    tr_spec = param_pspecs(cfg, mesh, trainable_sds, mode=mode)
    state_spec = type(state_sds)(
        global_tr=tr_spec,
        clients_tr=client_stack_pspecs(cfg, mesh, trainable_sds,
                                       multi_pod=multi_pod, mode=mode),
        tau=P(), t=P(),
        extra=jax.tree.map(lambda x: P(), state_sds.extra),
        markov=P(), rng=P())
    frozen_spec = param_pspecs(cfg, mesh, frozen_sds, fsdp=True)
    batch_spec = batch_pspecs(mesh, batch_sds, multi_pod=multi_pod,
                              mode=batch_mode)

    fn = jax.jit(
        round_fn,
        in_shardings=(_ns(mesh, state_spec), _ns(mesh, frozen_spec),
                      _ns(mesh, batch_spec)),
        donate_argnums=(0,),
    )
    return fn, (state_sds, frozen_sds, batch_sds)


def _chunk_k(variant):
    """'flat_chunk' -> 8 rounds per dispatch; 'flat_chunk<K>' -> K."""
    for tok in variant.split("+"):
        if tok.startswith("flat_chunk"):
            return int(tok[len("flat_chunk"):] or 8)
    return 0


def _chunk_sampling(variant):
    """'+epoch' selects epoch-permutation device sampling for flat_chunk."""
    return "epoch" if "epoch" in variant.split("+") else "uniform"


def _chunk_seeds(variant):
    """'+seeds<S>' selects the S-batched multi-seed executor (S seed
    replicates advanced per dispatch, seed axis over the client mesh
    axes); 0 = single-seed flat_chunk."""
    for tok in variant.split("+"):
        if tok.startswith("seeds"):
            return int(tok[len("seeds"):] or 4)
    return 0


def _chunk_mesh(variant):
    """'+mesh' (with '+seedsS') runs the S-batched executor on a dedicated
    ('seed','pod','data') mesh (launch/mesh.make_seed_mesh) instead of
    folding the seed axis onto the client axes — the inner [m, N] client
    placement survives under the seed axis."""
    return "mesh" in variant.split("+")


def _chunk_faults(variant):
    """'+faults' lowers the chunked executor with fault injection live
    (core/faults.py): mid-round dropout + sanitization split the masks,
    a device-resident [T, m] replay trace rides the donated scan carry
    (sharded client-wise by flat_pspecs), and the metrics dict grows the
    n_dropped/n_rejected counters."""
    return "faults" in variant.split("+")


def _chunk_staleness(variant):
    """'+staleness' lowers the chunked executor with semi-async rounds
    live (core/staleness.py): bounded-delay straggler uploads park in a
    device-resident [tau_max, m, N] pending ring buffer riding the
    donated scan carry (sharded client-wise by flat_pspecs), and the
    metrics dict grows the n_stale/mean_staleness counters."""
    return "staleness" in variant.split("+")


def build_chunk_train_step(cfg, shape, mesh, multi_pod, variant):
    """The donated, sharded, scan-chunked round executor on the flat
    substrate: K FedAWE rounds per dispatch, the [m, N] client stack over
    ('pod','data') (flat_pspecs) and donated in->out, batches gathered on
    device from a resident store inside the scan."""
    K = _chunk_k(variant)
    m = fl_clients(mesh)
    b = max(1, shape.global_batch // m)
    s = cfg.local_steps
    fl = FLConfig(m=m, s=s, eta_l=0.01, eta_g=1.0, strategy="fedawe",
                  lr_schedule=False, grad_clip=0.0, flat_state=True)
    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    trainable_sds, frozen_sds = split_trainable(params_sds, cfg)

    def loss_fn(tr, fz, batch, rng):
        return lm_loss(merge_trainable(tr, fz, cfg), cfg, batch)

    av = AvailabilityCfg(kind="sine", gamma=0.3, period=20)
    base_p = jnp.full((m,), 0.5, F32)
    fault_cfg, fault_sds = None, None
    if _chunk_faults(variant):
        from repro.core.faults import FaultCfg
        fault_cfg = FaultCfg(upload_survival=0.9, trace=True,
                             sanitize=True)
        # [T, m] replay trace riding the donated scan carry; rows are
        # consumed mod T, so a 2K-round trace covers any dispatch count
        fault_sds = {"trace": _sds((2 * K, m), F32)}
    staleness_cfg, stale_sds = None, None
    if _chunk_staleness(variant):
        from repro.core.flatten import FlatSpec
        from repro.core.staleness import StalenessCfg
        staleness_cfg = StalenessCfg(tau_max=2, kind="det", delay=1)
        # [tau_max, m, N] pending ring buffer + [tau_max, m] slot ages
        # riding the donated scan carry, sharded client-wise
        n_flat = FlatSpec.from_tree(trainable_sds).size
        stale_sds = {"buf": _sds((staleness_cfg.tau_max, m, n_flat), F32),
                     "ages": _sds((staleness_cfg.tau_max, m), F32)}
    S = _chunk_seeds(variant)
    round_fn = make_round_fn_with_frozen(
        fl, loss_fn, av, base_p, fault_cfg=fault_cfg,
        staleness_cfg=staleness_cfg,
        client_shards=mesh_client_shards(mesh, seeds=bool(S),
                                         multi_pod=multi_pod))
    sampling = _chunk_sampling(variant)
    # the dry-run store gives every client exactly `cap` samples (below),
    # so the epoch permutation stack lowers at its production size
    init_sampler, sample_fn = make_device_sampler(m, s, b, mode=sampling,
                                                  min_count=4)

    state_sds = jax.eval_shape(
        lambda tr: init_fl_state(jax.random.PRNGKey(0), fl, tr,
                                 fault=fault_sds, stale=stale_sds),
        trainable_sds)

    # device-resident store: per-sample arrays (drop the [m, s, b] lead of
    # the round-batch spec), a padded per-client index matrix, counts
    cap = 4                       # samples per client in the dry-run store
    n = m * cap
    batch_sds = train_input_specs(cfg, shape, m)
    store_sds = dict(
        arrays={k: _sds((n,) + v.shape[3:], v.dtype)
                for k, v in batch_sds.items()},
        idx=_sds((m, cap), I32),
        counts=_sds((m,), I32),
    )
    key_sds = _sds((2,), jnp.uint32)
    # carried SamplerState (epoch: [m, cap] permutation + [m] cursors;
    # uniform: empty) — born from the same eval_shape path the runtime uses
    sampler_sds = jax.eval_shape(init_sampler, store_sds, key_sds)

    ca = ("pod", "data") if multi_pod else ("data",)
    state_spec = flat_pspecs(mesh, state_sds, multi_pod=multi_pod)
    frozen_spec = param_pspecs(cfg, mesh, frozen_sds, fsdp=True)
    sampler_spec = sampler_pspecs(mesh, sampler_sds, m, multi_pod=multi_pod)
    store_spec = dict(
        arrays=jax.tree.map(lambda v: P(*([None] * len(v.shape))),
                            store_sds["arrays"]),
        idx=P(ca, None),
        counts=P(ca),
    )
    metrics_spec = dict(loss=P(None), n_active=P(None), mean_echo=P(None),
                        n_computed=P(None))
    if fault_cfg is not None:
        metrics_spec.update(n_dropped=P(None), n_rejected=P(None))
    if staleness_cfg is not None:
        metrics_spec.update(n_stale=P(None), mean_staleness=P(None))

    if S:
        # S-batched multi-seed executor: FLState/SamplerState/data keys
        # grow a leading [S] axis.  On the plain mesh it takes over the
        # client mesh axes (seed_pspecs strips the displaced inner client
        # placement); on a '+mesh' seed mesh it rides the dedicated
        # 'seed' axis and the inner ('pod','data') client placement
        # SURVIVES.  The store and the frozen base stay shared across
        # replicates either way.
        def _seed_sds(t):
            return jax.tree.map(lambda x: _sds((S,) + x.shape, x.dtype), t)

        sa = "seed" if "seed" in mesh.axis_names else ca
        state_spec = seed_pspecs(state_spec, seed_axes=sa)
        sampler_spec = seed_pspecs(sampler_spec, seed_axes=sa)
        metrics_spec = seed_pspecs(metrics_spec, seed_axes=sa)
        fn = make_seeds_chunk_fn(
            fl, round_fn, sample_fn, K, S, with_frozen=True, donate=True,
            in_shardings=(_ns(mesh, state_spec), _ns(mesh, frozen_spec),
                          _ns(mesh, sampler_spec), _ns(mesh, store_spec),
                          NamedSharding(mesh, P(None, None))),
            out_shardings=(_ns(mesh, state_spec), _ns(mesh, sampler_spec),
                           _ns(mesh, metrics_spec)))
        return fn, (_seed_sds(state_sds), frozen_sds, _seed_sds(sampler_sds),
                    store_sds, _sds((S, 2), jnp.uint32))

    fn = make_chunk_fn(
        fl, round_fn, sample_fn, K, with_frozen=True, donate=True,
        in_shardings=(_ns(mesh, state_spec), _ns(mesh, frozen_spec),
                      _ns(mesh, sampler_spec), _ns(mesh, store_spec),
                      NamedSharding(mesh, P(None))),
        out_shardings=(_ns(mesh, state_spec), _ns(mesh, sampler_spec),
                       _ns(mesh, metrics_spec)))
    return fn, (state_sds, frozen_sds, sampler_sds, store_sds, key_sds)


def build_prefill_step(cfg, shape, mesh, variant="baseline"):
    B = shape.global_batch
    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, B, shape.seq_len))
    inp = prefill_input_specs(cfg, shape)

    def step(params, cache, batch):
        return prefill(params, cfg, cache, batch["tokens"],
                       embeds=batch.get("embeds"),
                       enc_embeds=batch.get("enc_embeds"))

    fsdp = cfg.fl_mode == "lora"
    p_spec = param_pspecs(cfg, mesh, params_sds, fsdp=fsdp)
    c_spec = cache_pspecs(cfg, mesh, cache_sds, B)
    tok_spec, _ = serve_batch_pspecs(mesh, B)
    seq_ax = "model" if "seq_shard" in variant else None
    b_spec = {}
    for k, v in inp.items():
        rest = [None] * (len(v.shape) - 1)
        if k == "tokens" and seq_ax and v.shape[1] % 16 == 0:
            rest[0] = seq_ax  # sequence-parallel prefill activations
        b_spec[k] = P(tok_spec[0], *rest)
    fn = jax.jit(step,
                 in_shardings=(_ns(mesh, p_spec), _ns(mesh, c_spec),
                               _ns(mesh, b_spec)),
                 donate_argnums=(1,))
    return fn, (params_sds, cache_sds, inp)


def build_decode_step(cfg, shape, mesh):
    B = shape.global_batch
    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache_sds = jax.eval_shape(
        lambda: init_cache(cfg, B, shape.seq_len))
    inp = decode_input_specs(cfg, shape)

    def step(params, cache, tokens, pos):
        return serve_step(params, cfg, cache, tokens, pos)

    fsdp = cfg.fl_mode == "lora"
    p_spec = param_pspecs(cfg, mesh, params_sds, fsdp=fsdp)
    c_spec = cache_pspecs(cfg, mesh, cache_sds, B)
    tok_spec, pos_spec = serve_batch_pspecs(mesh, B)
    fn = jax.jit(step,
                 in_shardings=(_ns(mesh, p_spec), _ns(mesh, c_spec),
                               _ns(mesh, tok_spec), _ns(mesh, pos_spec)),
                 donate_argnums=(1,))
    return fn, (params_sds, cache_sds, inp["tokens"], inp["pos"])


# ---------------------------------------------------------------------------
# run one combination
# ---------------------------------------------------------------------------

def run_one(arch, shape_name, mesh_kind, *, test_mesh=False, verbose=True,
            variant="baseline"):
    cfg = _apply_cfg_variant(get_config(arch), variant)
    shape = SHAPES[shape_name]
    multi_pod = mesh_kind == "multi"
    if _chunk_mesh(variant) and _chunk_seeds(variant):
        # dedicated ('seed','pod','data') mesh for the S-batched executor
        mesh = make_seed_mesh(_chunk_seeds(variant), multi_pod=multi_pod,
                              test=test_mesh)
    else:
        mesh = (make_test_mesh(multi_pod=multi_pod) if test_mesh
                else make_production_mesh(multi_pod=multi_pod))
    rec = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
               chips=n_chips(mesh), ok=False, variant=variant,
               mesh_axes=dict(zip(mesh.axis_names,
                                  (int(d) for d in mesh.devices.shape))))
    t0 = time.time()
    try:
        with mesh:
            if shape.kind == "train":
                K = _chunk_k(variant)
                if K:
                    fn, args = build_chunk_train_step(cfg, shape, mesh,
                                                      multi_pod, variant)
                    rec["chunk_rounds"] = K
                    rec["sampling"] = _chunk_sampling(variant)
                    if _chunk_seeds(variant):
                        rec["seeds"] = _chunk_seeds(variant)
                    if _chunk_faults(variant):
                        rec["faults"] = True
                    if _chunk_staleness(variant):
                        rec["staleness"] = True
                else:
                    fn, args = build_train_step(cfg, shape, mesh, multi_pod,
                                                variant=variant)
                rec["clients"] = fl_clients(mesh)
                toks = (fl_clients(mesh) * cfg.local_steps
                        * max(1, shape.global_batch // fl_clients(mesh))
                        * shape.seq_len) * max(1, K) \
                    * max(1, _chunk_seeds(variant))
                rec["model_flops"] = analysis.model_flops(cfg, toks, "train")
            elif shape.kind == "prefill":
                fn, args = build_prefill_step(cfg, shape, mesh,
                                              variant=variant)
                toks = shape.global_batch * shape.seq_len
                rec["model_flops"] = analysis.model_flops(cfg, toks,
                                                          "inference")
            else:
                fn, args = build_decode_step(cfg, shape, mesh)
                rec["model_flops"] = analysis.model_flops(
                    cfg, shape.global_batch, "inference")

            lowered = fn.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

            rec["cost"] = {k: v for k, v in
                           analysis.cost_analysis_numbers(compiled).items()
                           if not k.startswith("bytes accessed")
                           or k == "bytes accessed"}
            rec["memory"] = analysis.memory_analysis_numbers(compiled)
            hlo = compiled.as_text()
            rec["collectives"] = analysis.collective_bytes(hlo)
            rec["collective_top"] = analysis.collective_top(hlo)
            rec["hlo_bytes_len"] = len(hlo)

            # raw HLO-based terms (NB: while-loop bodies are counted once by
            # HloCostAnalysis — undercounts scanned stacks; kept for record)
            flops = rec["cost"].get("flops", 0.0)
            acc_bytes = rec["cost"].get("bytes accessed", 0.0)
            rec["roofline_hlo"] = analysis.roofline_terms(
                flops, acc_bytes, rec["collectives"]["total"])

            # analytic model (primary; collective bytes cross-checked
            # against the trip-count-corrected HLO parse)
            from repro.launch import roofline as rl
            ax = dict(zip(mesh.axis_names, mesh.devices.shape))
            ana = rl.analytic_costs(cfg, shape, ax)
            if shape.kind == "train" and _chunk_k(variant):
                # analytic model is per round; a chunked dispatch covers K
                # rounds (x S seed replicates under +seedsS)
                mul = _chunk_k(variant) * max(1, _chunk_seeds(variant))
                ana = {k: v * mul if isinstance(v, (int, float)) else v
                       for k, v in ana.items()}
            # baseline: cross-check analytic vs measured; variants change
            # the collective schedule, so trust the (trip-count-corrected)
            # HLO measurement alone there.
            if variant == "baseline":
                coll = max(ana["coll_bytes_per_dev"],
                           float(rec["collectives"]["total"]))
            else:
                coll = float(rec["collectives"]["total"])
            rec["analytic"] = ana
            rec["roofline"] = analysis.roofline_terms(
                ana["flops_per_dev"], ana["hbm_bytes_per_dev"], coll)
            if rec["model_flops"]:
                rec["useful_flops_ratio"] = rec["model_flops"] / (
                    ana["flops_per_dev"] * n_chips(mesh))
            rec["ok"] = True
            if verbose:
                print(json.dumps(
                    {k: rec[k] for k in
                     ("arch", "shape", "mesh", "lower_s", "compile_s",
                      "roofline", "collectives", "memory")
                     if k in rec}, indent=1, default=str))
                print(compiled.memory_analysis())
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"FAILED {arch} {shape_name} {mesh_kind}: {rec['error']}")
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true",
                    help="sweep every supported (arch x shape) pair")
    ap.add_argument("--test-mesh", action="store_true",
                    help="use the tiny CI mesh (requires REPRO_DRYRUN_DEVICES)")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    help="'+'-joined §Perf knobs: dp_client, moe_hint, "
                         "dots_remat, seq_shard, flat_chunk[K] (donated "
                         "scan-chunked flat-substrate executor, K rounds "
                         "per dispatch), epoch (epoch-permutation device "
                         "sampling with the carried SamplerState), seedsS "
                         "(S-batched multi-seed executor: S replicates per "
                         "dispatch, seed axis over the client mesh axes), "
                         "mesh (with seedsS: dedicated ('seed','pod','data') "
                         "mesh from make_seed_mesh — the inner client "
                         "placement survives under the seed axis), faults "
                         "(fault injection live in the chunked executor: "
                         "mid-round dropout + sanitization masks, [T, m] "
                         "replay trace in the donated carry, "
                         "n_dropped/n_rejected metrics), staleness "
                         "(semi-async rounds live in the chunked executor: "
                         "bounded-delay straggler uploads through a "
                         "[tau_max, m, N] pending ring buffer in the "
                         "donated carry, n_stale/mean_staleness metrics)")
    args = ap.parse_args()

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("variant", "baseline"))
            for r in results if r.get("ok")}

    if args.all:
        from repro.configs import ARCHS
        combos = [(a, s, args.mesh) for a in ARCHS
                  for s in supported_shapes(a)]
    else:
        assert args.arch and args.shape
        combos = [(args.arch, args.shape, args.mesh)]

    for arch, shape_name, mesh_kind in combos:
        if args.skip_done and (arch, shape_name, mesh_kind,
                               args.variant) in done:
            print(f"skip {arch} {shape_name} {mesh_kind} (done)")
            continue
        print(f"=== dry-run {arch} x {shape_name} x {mesh_kind} ===",
              flush=True)
        rec = run_one(arch, shape_name, mesh_kind,
                      test_mesh=args.test_mesh, variant=args.variant)
        results = [r for r in results
                   if not (r["arch"] == arch and r["shape"] == shape_name
                           and r["mesh"] == mesh_kind
                           and r.get("variant", "baseline") == args.variant)]
        results.append(rec)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1, default=str)
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"dry-run complete: {n_ok}/{len(results)} combinations OK")
    if any(not r.get("ok") for r in results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
