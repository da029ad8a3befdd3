"""One run of one benchmark cell: set-up, the measured window, the trace,
and the check of what the window's program produced.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic, ``configs/<config>.json`` holds the
model and the deployment, ``traffic/<traffic>.json`` the availability
process, the data and the chunk length, ``workloads/<cell>.json`` only
the limits of the check, and ``metrics/<metric>.py`` each per-layer
reader.

The window drives the program's own entry: ``engine.run_rounds`` with
the cell's compiled ``make_chunk_fn`` program on one chip, or
``experiments.run_seed_rounds`` with the seed executor on the seed mesh.
Per-chunk host stamps come from the executors' chunk-boundary hook.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import os
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

from bench import models, reference, traffic as traffic_mod

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SAMPLED_ROWS = 128    # client rows the check reads back per seed
NOT_FINITE = 1e30     # how a compared number that is inf or nan is shown


def _json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload entry of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    per_layer: list      # per-layer metric entries that apply to the cell


def load_benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = _json(os.path.join(ROOT, conf["file"]))
    traffic = _json(os.path.join(BENCH_DIR, "traffic",
                                 f"{entry['traffic']}.json"))
    cell_file = _json(os.path.join(BENCH_DIR, "workloads", f"{name}.json"))
    per_layer = [p for p in bench["per_layer"]
                 if name in p.get("workloads", [name])]
    return Cell(name=name, chips=int(entry["chips"]), cfg=cfg,
                traffic=traffic, limits=cell_file["limits"],
                per_layer=per_layer)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Program:
    """The program's objects for one cell and seed: round function, device
    store, sampler, state and the compiled chunk program, built as the
    train CLI builds them (one chip) or as ``run_multi_seed`` does (seed
    mesh).  Only the generated arrays of ``task`` come from the bench.

    The frozen base is a runtime argument of the round and the chunk
    program (``make_round_fn_with_frozen``, ``make_chunk_fn(...,
    with_frozen=True)``), placed on the device and never donated.  An
    empty base has no leaves, so it adds no parameter to the program."""

    def __init__(self, cell: Cell, task: traffic_mod.Task):
        import jax
        import jax.numpy as jnp
        from repro.core import engine
        from repro.core.availability import AvailabilityCfg
        from repro.data import federated

        cfg, tr = cell.cfg, cell.traffic
        dep, trn = cfg["deployment"], cfg["training"]
        self.K = int(tr["chunk_rounds"])
        self.seeds = int(dep["seeds"])
        self.mesh = dep["mesh"] == "seed"
        self.fl = engine.FLConfig(
            m=dep["m"], s=trn["s"], eta_l=trn["eta_l"], eta_g=trn["eta_g"],
            strategy=dep["strategy"], lr_schedule=trn["lr_schedule"],
            use_kernel=dep["echo_kernel"], flat_state=True,
            grad_clip=trn["grad_clip"], sparse_cohort=dep["c_max"],
            resident_dtype=dep["resident_dtype"])
        loss_fn = models.of(cfg).loss_fn(cfg["model"])
        avail = AvailabilityCfg(**{k: v for k, v in
                                   tr["availability"].items()
                                   if k != "base_p"})
        self.round_fn = engine.make_round_fn_with_frozen(
            self.fl, loss_fn, avail, task.base_p)
        # the program uploads its own copy of the arrays from the host;
        # the bench's device copies go first, so set-up never holds both
        arrays = {k: np.asarray(v) for k, v in task.arrays.items()}
        task.free_arrays()
        self.store = federated.device_store(arrays, task.client_indices)
        del arrays
        init_sampler, self.sample_fn = federated.make_device_sampler(
            self.fl.m, self.fl.s, trn["batch"], mode=tr["sampling"],
            min_count=trn["batch"],
            emit="cols" if self.fl.sparse_cohort else "batches")
        if self.mesh:
            if jax.tree.leaves(task.frozen):
                raise SystemExit("the seed mesh takes no frozen base: no "
                                 "cell needs one there")
            self._build_seed_mesh(task, init_sampler)
            return
        if self.seeds != 1:
            raise SystemExit("a one-chip cell runs one seed")
        # the executor donates the state, its key included: the
        # program gets device copies of the task's host keys
        self.state = engine.init_fl_state(jnp.asarray(task.state_key),
                                          self.fl, task.params)
        self.data_key = jnp.asarray(task.data_key)
        self.sampler_state = init_sampler(self.store, self.data_key)
        self.frozen = jax.device_put(task.frozen)
        self.chunk = engine.make_chunk_fn(
            self.fl, self.round_fn, self.sample_fn, self.K,
            with_frozen=True).lower(
            self.state, self.frozen, self.sampler_state, self.store,
            self.data_key).compile()
        frozen, chunk = self.frozen, self.chunk
        self.chunk_fn = lambda state, sampler_state, store, data_key: chunk(
            state, frozen, sampler_state, store, data_key)

    def _build_seed_mesh(self, task, init_sampler):
        import jax.numpy as jnp
        from repro.launch import experiments
        from repro.launch.mesh import make_seed_mesh

        states, ss, keys = experiments.build_seed_batch(
            self.fl, task.params, jnp.asarray(task.state_key),
            jnp.asarray(task.data_key),
            init_sampler, self.store, self.seeds)
        # the seed executor's rounds take no frozen argument; the base is {}
        builder = experiments.build_seed_executor(
            self.fl, lambda state, batches: self.round_fn(state, {}, batches),
            self.sample_fn, self.seeds,
            mesh=make_seed_mesh(self.seeds), states=states,
            sampler_states=ss, store=self.store, data_keys=keys)
        (self.state, self.sampler_state, self.store,
         self.data_key) = experiments.place_seed_batch(
            builder.in_shardings, states, ss, self.store, keys)
        self.chunk = builder(self.K).lower(
            self.state, self.sampler_state, self.store,
            self.data_key).compile()
        self.chunk_fn = self.chunk

    def memory(self) -> dict:
        ma = self.chunk.memory_analysis()
        return {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}

    def run(self, T: int, stamp=None) -> List[list]:
        """T rounds through the program's executor; per-seed histories.
        ``stamp`` is called at every chunk boundary."""
        from repro.core import engine

        def hook(state, done, sampler_state):
            # the executors donate the sampler carry and do not return it
            self.sampler_state = sampler_state
            if stamp is not None:
                stamp()

        if self.mesh:
            from repro.launch import experiments
            self.state, hists = experiments.run_seed_rounds(
                self.state, self.chunk_fn, T, self.K,
                sampler_states=self.sampler_state, store=self.store,
                data_keys=self.data_key, n_seeds=self.seeds,
                ckpt_fn=hook, ckpt_every=self.K)
            return hists
        self.state, hist = engine.run_rounds(
            self.state, self.round_fn, None, T, chunk_rounds=self.K,
            chunk_fn=self.chunk_fn, sample_fn=self.sample_fn, store=self.store,
            data_key=self.data_key, sampler_state=self.sampler_state,
            ckpt_fn=hook, ckpt_every=self.K)
        return [hist]

    def observe(self, row_ids) -> list:
        """Per seed: (global [N], tau [m], rows [R, N] as f32)."""
        import jax
        import jax.numpy as jnp

        st = self.state
        glob, tau = st.global_tr, st.tau
        rows = jnp.take(st.clients_tr, jnp.asarray(row_ids), axis=-2)
        glob, tau, rows = jax.device_get(
            (glob, tau, rows.astype(jnp.float32)))
        if not self.mesh:
            glob, tau, rows = glob[None], tau[None], rows[None]
        return [(np.asarray(glob[j]), np.asarray(tau[j]),
                 np.asarray(rows[j])) for j in range(self.seeds)]

    def seed_chips(self) -> int:
        """Distinct devices that each hold exactly one whole seed of the
        global model (the seed mesh's placement)."""
        arr = self.state.global_tr
        devs = set()
        for sh in arr.addressable_shards:
            if sh.data.shape[0] == 1 and sh.data.shape[1:] == arr.shape[1:]:
                devs.add(sh.device)
        return len(devs)

    def finite(self) -> bool:
        import jax
        import jax.numpy as jnp

        return bool(jax.device_get(jnp.all(jnp.isfinite(
            self.state.global_tr))))

    def free(self):
        import jax

        # the frozen base is the task's, which the reference is given
        for leaf in jax.tree.leaves((self.state, self.sampler_state,
                                     self.store)):
            leaf.delete()
        self.state = self.sampler_state = self.store = self.frozen = None
        self.chunk = self.chunk_fn = None


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Backend compiles, from jax's monitoring events, so that the window
    can show it compiled nothing.  One listener a process."""
    _instance = None

    def __init__(self):
        from jax._src import monitoring
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_duration(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.compiles += 1


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def hbm_peak_bytes(stats: dict) -> int:
    """A chip's HBM high-water mark: the allocator's peak of live buffers
    plus its peak reservation for the executables' temporaries.  The TPU
    allocator keeps the two in disjoint regions (``bytes_reserved`` never
    counts toward ``bytes_in_use``), so the buffers alone leave out the
    chunk program's working set."""
    return int(stats["peak_bytes_in_use"]) + int(stats["peak_bytes_reserved"])


class GcPauses:
    """Python's garbage collections while active, as (generation, ms), so
    that a window that stalls on the host can be told from one that
    stalls on the chip."""

    def __init__(self):
        self.pauses, self._t = [], None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                round(1e3 * (time.perf_counter() - self._t),
                                      3)))

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


def _device_info(devices) -> dict:
    # the CPU, on which the tests drive a run, keeps no allocator stats
    peak = max(hbm_peak_bytes(stats) if (stats := d.memory_stats()) else 0
               for d in devices)
    d0 = devices[0]
    import jax
    return dict(platform=d0.platform, kind=d0.device_kind,
                count=len(jax.devices()), memory_peak_bytes=peak)


def sample_rows(cell: Cell, seed: int) -> np.ndarray:
    """The client rows the check reads back, drawn from the seed."""
    m = cell.cfg["deployment"]["m"]
    rng = np.random.default_rng([int(seed), 7])
    return np.sort(rng.choice(m, size=min(m, SAMPLED_ROWS), replace=False))


def setup(cell: Cell, seed: int):
    """Inputs from the seed, the program built and compiled, and its first
    chunk run: the rounds the check follows.  Returns (task, program,
    first-chunk histories, observations after it, sampled row ids)."""
    t0 = time.perf_counter()
    task = traffic_mod.make_task(cell.cfg, cell.traffic, seed)
    t1 = time.perf_counter()
    prog = Program(cell, task)
    t2 = time.perf_counter()
    row_ids = sample_rows(cell, seed)
    first = prog.run(prog.K)
    obs = prog.observe(row_ids)
    _log(f"setup phases: task {t1 - t0:.3f} s, program built "
         f"{t2 - t1:.3f} s, first chunk {time.perf_counter() - t2:.3f} s; "
         f"first chunk losses {[[r['loss'] for r in h] for h in first]}")
    return task, prog, first, obs, row_ids


def _seed_keys(cell: Cell, task, j):
    """Seed replicate j's state and data keys, as the program derives
    them (``build_seed_batch`` folds the replicate id into both)."""
    import jax

    if cell.cfg["deployment"]["mesh"] == "seed":
        return (jax.random.fold_in(task.state_key, j),
                jax.random.fold_in(task.data_key, j))
    return task.state_key, task.data_key


def follow(cell: Cell, seed: int, task, row_ids, **variant) -> list:
    """The reference's observations of every seed replicate's first chunk
    (``variant``: ``dtype``, ``half_batch``)."""
    task.arrays = traffic_mod.sample_arrays(cell.cfg, cell.traffic, seed,
                                            task.groups)
    out = []
    for j in range(int(cell.cfg["deployment"]["seeds"])):
        sk, dk = _seed_keys(cell, task, j)
        out.append(reference.follow(
            cell.cfg, cell.traffic, arrays=task.arrays,
            client_indices=task.client_indices, base_p=task.base_p,
            params=task.params, frozen=task.frozen, state_key=sk,
            data_key=dk, rounds=int(cell.traffic["chunk_rounds"]),
            row_ids=row_ids, **variant))
    task.free_arrays()
    return out


def program_observed(first, obs, row_ids) -> list:
    """The program's first chunk, per seed, as ``reference.Observed``."""
    out = []
    for h, (g, tau, rows) in zip(first, obs):
        out.append(reference.Observed(
            loss=np.array([r["loss"] for r in h]),
            n_active=np.array([r["n_active"] for r in h]),
            n_deferred=np.array([r.get("n_deferred", 0.0) for r in h]),
            tau=tau, global_flat=g, rows=rows, row_ids=row_ids))
    return out


def compare(cell: Cell, task, got: list, want: list) -> dict:
    return reference.compare(
        got, want, reference.flatten(task.params),
        reference.leaf_offsets(task.params),
        cell.cfg["deployment"]["resident_dtype"])


def verdict(numbers: dict, limits: dict):
    """``correct`` and the compared numbers beside their limits.

    A number whose limit is ``null`` in the cell's file is not compared
    (``PERF.md`` says why); it is logged, not shown.  A number the file
    does not name fails the check."""
    shown, ok = {}, True
    for name, value in numbers.items():
        if name in limits and limits[name] is None:
            _log(f"not compared: {name} {value}")
            continue
        limit = limits.get(name)
        if limit is None or not value <= limit:
            ok = False
        if not math.isfinite(value):
            value = NOT_FINITE      # JSON has no inf or nan
        shown[name] = {"value": float(value), "limit": limit}
    return ok, shown


def _read_per_layer(cell, run_info) -> dict:
    out = {}
    for entry in cell.per_layer:
        mod = importlib.import_module(f"bench.metrics.{entry['name']}")
        value = mod.read(run_info)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


@dataclasses.dataclass
class RunInfo:
    """What a per-layer reader may read: the run's record and its trace."""
    cell: Cell
    rounds: int                 # rounds per seed in the window
    seeds: int
    chips: int
    window_s: float             # host clock, first dispatch to last result
    histories: list             # per seed, the window's per-round metrics
    chunk_stamps: list          # host clock at every chunk boundary
    memory: dict                # the chunk program's memory analysis
    hlo: str                    # the compiled chunk program's HLO text
    peaks: dict
    trace: Optional[object] = None   # bench.traces.Trace of the window
    n_trainable: Optional[int] = None   # N, a client row's parameters


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        devices) -> dict:
    """One run: set-up, the window, memory, check, and the result line."""
    import jax
    from repro.launch import compilecache

    from bench import flops

    cache_dir = compilecache.enable()
    counter = CompileCounter.get()
    peaks = flops.peaks(devices[0].device_kind) \
        if devices[0].platform == "tpu" else None
    task, prog, first, obs, row_ids = setup(cell, seed)
    seed_chips = prog.seed_chips() if prog.mesh else None
    t = time.perf_counter()
    prog.run(prog.K)
    chunk_s = time.perf_counter() - t
    T = prog.K * max(1, round(seconds / chunk_s))
    setup_s = time.perf_counter() - t0
    memory = prog.memory()
    _log(f"setup: {setup_s:.3f} s, chunk {chunk_s:.4f} s, window T={T} "
         f"rounds x {prog.seeds} seeds, cache {cache_dir} "
         f"{compilecache.counters()}, chunk program bytes {memory}")

    stamps = []
    tracer = None
    c0, h0 = counter.compiles, compilecache.counters()["hits"]
    if trace:
        tracer = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tracer)
    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_window"), GcPauses() as gcs:
        hists = prog.run(T, stamp=lambda: stamps.append(time.perf_counter()))
        jax.block_until_ready(prog.state)
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t_start
    hlo = prog.chunk.as_text() if trace else ""
    chunk_ms = [round(1e3 * (b - a), 3)
                for a, b in zip([t_start] + stamps, stamps)]
    _log(f"window: {window_s:.4f} s, {len(stamps)} chunks, compiles "
         f"{counter.compiles - c0}, cache hits "
         f"{compilecache.counters()['hits'] - h0}; chunk ms {chunk_ms}; "
         f"gc (generation, ms) {gcs.pauses}")
    if counter.compiles - c0:
        raise SystemExit("the measured window compiled a program")

    dev = _device_info(devices)
    _log(f"memory stats of the first chip: {devices[0].memory_stats()}")
    finite = prog.finite()
    failed = sum(1 for h in hists for r in h
                 if not math.isfinite(r["loss"]))
    if not finite:
        failed = max(failed, 1)
    attempted = T * prog.seeds
    prog.free()
    numbers = compare(cell, task, program_observed(first, obs, row_ids),
                      follow(cell, seed, task, row_ids))
    if seed_chips is not None:
        numbers["placement_mismatch"] = prog.seeds - seed_chips
    correct, shown = verdict(numbers, cell.limits)
    correct = correct and failed == 0

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        from bench import traces
        tr = traces.load_dir(tracer, window_name="bench_window")
        info = RunInfo(cell=cell, rounds=T, seeds=prog.seeds,
                       chips=cell.chips, window_s=window_s,
                       histories=hists, chunk_stamps=stamps, memory=memory,
                       hlo=hlo, peaks=peaks, trace=tr,
                       n_trainable=len(reference.flatten(task.params)))
        result["metrics"] = _read_per_layer(cell, info)
        busy = tr.busy_s()
        dev.update(busy_s=busy, window_s=tr.window_s())
        result["device"] = dev
        result["breakdown"] = tr.breakdown()
        traces.remove_dir(tracer)
    else:
        result["metrics"] = {
            "rounds_per_s": {"value": attempted / window_s,
                             "unit": "rounds/s"},
            "hbm_peak_gb": {"value": dev["memory_peak_bytes"] / 1e9,
                            "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["device"] = dev
    result["check"] = shown
    return result
