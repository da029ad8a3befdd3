"""Per-layer time from the program's own names: scopes read from
hand-written HLO, host spans from a trace recorded here on the CPU, both
from a trace recorded on a v5e chip, and the names themselves against the
program's."""
import json
import math
import os
import re

import numpy as np
import pytest

from bench import harness, scopes, traces
from bench.metrics import (aggregate_ms_per_round, chunk_host_ms,
                           cohort_move_ms_per_round, local_sgd_ms_per_round,
                           sampler_ms_per_round)
from bench.traces import MissingOp, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(harness.ROOT, "src", "repro")
READERS = (local_sgd_ms_per_round, aggregate_ms_per_round,
           sampler_ms_per_round, cohort_move_ms_per_round, chunk_host_ms)

HLO = """HloModule jit_chunk

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %convolution.3 = f32[4]{0} convolution(f32[4]{0} %param_0, f32[4]{0} %param_0), window={size=1}, metadata={op_name="jit(chunk)/while/body/fl_aggregate/conv"}
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(f32[4]{0} %param_0.1, f32[4]{0} %param_0.1), metadata={op_name="jit(chunk)/while/body/fl_aggregate/mul"}
}

%fused_computation.3 (param_0.2: f32[4]) -> f32[4] {
  %param_0.2 = f32[4]{0} parameter(0)
  ROOT %convert.1 = f32[4]{0} convert(f32[4]{0} %param_0.2)
}

%step_body (q: (f32[4])) -> (f32[4]) {
  %q = (f32[4]{0}) parameter(0)
  %copy.11 = f32[4]{0} copy(f32[4]{0} %q)
  ROOT %convert_reduce_fusion.2 = f32[4]{0} fusion(f32[4]{0} %copy.11), kind=kLoop, calls=%fused_computation.3
}

%body (p: (f32[4])) -> (f32[4]) {
  %p = (f32[4]{0}) parameter(0)
  %fusion.241 = f32[4]{0} fusion(f32[4]{0} %p), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/vmap(fl_local_sgd)/transpose(jvp(conv_general_dilated))"}
  %gather.5 = f32[4]{0} gather(f32[4]{0} %p), metadata={op_name="jit(chunk)/while/body/fl_sample/jit(_take)/gather" source_file="engine.py"}
  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/transpose(jvp(vmap(fl_aggregate)))/mul"}
  %custom-call.9 = f32[4]{0} custom-call(f32[4]{0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/while/body/fl_aggregate/pallas_call"}
  %add.2 = f32[4]{0} add(f32[4]{0} %p, f32[4]{0} %p), metadata={op_name="jit(chunk)/while/body/add"}
  %copy.4 = f32[4]{0} copy(f32[4]{0} %p)
  %fusion.8 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/while/body/fl_aggregate/vmap(fl_cohort_scatter)/scatter"}
  %while.97 = (f32[4]{0}) while((f32[4]{0}) %p), condition=%step_cond, body=%step_body, metadata={op_name="jit(chunk)/while/body/vmap(fl_local_sgd)/while"}
  %dynamic-update-slice.18 = f32[8]{0} dynamic-update-slice(f32[8]{0} %custom-call.71, f32[4]{0} %while.97, s32[] %c0)
  %dynamic-update-slice.19 = f32[8]{0} dynamic-update-slice(f32[8]{0} %dynamic-update-slice.18, f32[4]{0} %p, s32[] %c4), metadata={op_name="jit(chunk)/while/body/fl_cohort_gather/vmap(fl_local_sgd)/concatenate"}
  ROOT %fusion.12 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, calls=%fused_computation.2
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.96 = (f32[4]{0}) while((f32[4]{0}) %tuple), condition=%cond, body=%body, metadata={op_name="jit(chunk)/while"}
  ROOT %fusion.10 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(chunk)/vmap(vmap(fl_local_sgd))/add"}
}
"""


@pytest.mark.parametrize("op_name,scope", [
    ("jit(chunk)/while/body/fl_local_sgd/transpose(jvp(conv))",
     "fl_local_sgd"),
    ("jit(chunk)/vmap()/while/body/vmap(fl_local_sgd)/dot_general",
     "fl_local_sgd"),
    ("jit(f)/transpose(jvp(vmap(fl_aggregate)))/reduce_sum", "fl_aggregate"),
    ("jit(chunk)/fl_aggregate/fl_cohort_scatter/scatter",
     "fl_cohort_scatter"),
    ("jit(chunk)/while/body/jit(_where)/select_n", None),
    ("jit(chunk)/while/body/flat_sample/gather", None),
    ("", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_instruction_scopes_of_hand_written_hlo():
    smap = scopes.instruction_scopes(HLO)
    # a fusion takes its own metadata, not its fused computation's
    assert smap["fusion.241"] == "fl_local_sgd"
    assert smap["convolution.3"] == "fl_aggregate"
    assert smap["gather.5"] == "fl_sample"
    assert smap["fusion.7"] == smap["custom-call.9"] == "fl_aggregate"
    assert smap["fusion.8"] == "fl_cohort_scatter"
    assert smap["fusion.10"] == "fl_local_sgd"
    # a fusion with no metadata takes its fused root's scope
    assert smap["fusion.12"] == "fl_aggregate"
    # inside a scoped loop, what has no scope of its own takes the loop's
    assert smap["copy.11"] == smap["convert_reduce_fusion.2"] == \
        "fl_local_sgd"
    # with no metadata, outside any scoped loop: the scope its users share
    assert smap["dynamic-update-slice.18"] == "fl_local_sgd"
    # ... and inside the unscoped round loop it stays unscoped
    assert smap["add.2"] is None and smap["copy.4"] is None
    assert smap["while.96"] is None and smap["p"] is None


def _trace(host=()):
    ops = {0: [(0.5, 7.5, "while.96 = while"),
               (1.0, 3.0, "fusion.241 = fusion kind=kOutput"),
               (3.0, 3.5, "gather.5 = gather"),
               (3.5, 4.0, "fusion.7 = fusion kind=kLoop"),
               (4.0, 4.25, "custom-call.9 = custom-call "
                           "custom_call_target=tpu_custom_call"),
               (4.25, 4.5, "add.2 = add"),
               (4.5, 5.0, "copy.4 = copy"),
               (5.0, 5.5, "fusion.8 = fusion kind=kLoop"),
               (5.5, 6.5, "while.97 = while"),
               (5.5, 5.75, "copy.11 = copy"),
               (5.75, 6.5, "convert_reduce_fusion.2 = fusion kind=kLoop"),
               (6.5, 7.0, "fusion.12 = fusion kind=kLoop"),
               (7.5, 8.0, "fusion.10 = fusion kind=kLoop"),
               (11.0, 12.0, "fusion.241 = fusion kind=kOutput")]}  # outside
    return Trace((0.0, 10.0), ops, {}, [(0.0, 10.0, "bench_window")]
                 + list(host))


def _run(trace, hlo=HLO, rounds=2, chips=1):
    return harness.RunInfo(
        cell=None, rounds=rounds, seeds=1, chips=chips,
        window_s=trace.window_s(), histories=[], chunk_stamps=[],
        memory={}, hlo=hlo, peaks=None, trace=trace)


def test_readers_sum_their_scopes_without_loops():
    run = _run(_trace())
    # 3.5 s of local SGD over 2 rounds; the loops around them not counted
    assert local_sgd_ms_per_round.read(run) == pytest.approx(1750.0)
    assert aggregate_ms_per_round.read(run) == pytest.approx(625.0)
    assert sampler_ms_per_round.read(run) == pytest.approx(250.0)
    assert cohort_move_ms_per_round.read(run) == pytest.approx(250.0)
    # the same from a stored map in place of the program's text
    smap = scopes.instruction_scopes(HLO)
    assert local_sgd_ms_per_round.read(_run(_trace(), hlo=smap)) == \
        pytest.approx(1750.0)
    assert scopes.ms_per_round(_run(_trace(), chips=2),
                               scopes.AGGREGATE) == pytest.approx(312.5)


@pytest.mark.parametrize("reader,dropped,match", [
    (cohort_move_ms_per_round, "fl_cohort_scatter",
     "fl_cohort_gather or fl_cohort_scatter"),
    (local_sgd_ms_per_round, "fl_local_sgd", "fl_local_sgd"),
    (sampler_ms_per_round, "fl_sample", "fl_availability or fl_sample"),
])
def test_a_scope_with_nothing_in_the_window_raises(reader, dropped, match):
    smap = {k: v for k, v in scopes.instruction_scopes(HLO).items()
            if v != dropped}
    with pytest.raises(MissingOp, match=match):
        reader.read(_run(_trace(), hlo=smap))


def test_a_program_without_the_names_gives_nothing_to_read():
    """A program older than its scopes and spans (the metrics are new):
    every reader gives None, so the result line leaves them out."""
    old = re.sub(r"fl_\w+", "layer", HLO)
    run = _run(_trace([(0.0, 0.1, "PjitFunction(chunk)")]), hlo=old)
    assert [reader.read(run) for reader in READERS] == [None] * 5


def test_coverage_and_unscoped_ops():
    tr, smap = _trace(), scopes.instruction_scopes(HLO)
    # busy [0.5, 8]; scoped [1, 4.25] + [5, 7] + [7.5, 8]
    assert scopes.coverage(tr, smap) == pytest.approx(5.75 / 7.5)
    assert scopes.unscoped(tr, smap) == [("copy.4 = copy", 0.5),
                                         ("add.2 = add", 0.25)]


def test_chunk_host_time_on_hand_made_spans():
    host = [(0.0, 0.1, "fl_chunk_dispatch"), (0.1, 5.6, "fl_chunk_fetch"),
            (5.6, 5.61, "fl_chunk_records"), (5.62, 5.7, "fl_chunk_dispatch"),
            (5.7, 9.6, "fl_chunk_fetch"), (9.7, 9.9, "fl_chunk_dispatch"),
            (9.9, 11.0, "fl_chunk_fetch")]        # its middle is outside
    tr = _trace(host)
    assert scopes.host_spans(tr, "fl_chunk_fetch") == [(0.1, 5.6),
                                                       (5.7, 9.6)]
    assert chunk_host_ms.read(_run(tr)) == pytest.approx(
        1e3 * (0.1 + 0.3) / 2)
    with pytest.raises(MissingOp, match="fl_chunk_fetch"):
        chunk_host_ms.read(_run(_trace(host[:2])))


# -- host spans of a CPU run ---------------------------------------------------

def _problem(seeds=0):
    """A tiny flat dense run: the chunk program, its state, the sampler's
    init, store, data key(s), K, the sampler and the round function."""
    import jax
    import jax.numpy as jnp

    from repro.core import (AvailabilityCfg, FLConfig, init_fl_state,
                            make_chunk_fn, make_round_fn,
                            make_seeds_chunk_fn, stack_seeds)
    from repro.data import device_store, make_device_sampler

    m, s, b, K = 4, 2, 4, 2
    rng = np.random.default_rng(0)
    arrays = dict(x=rng.normal(size=(32, 4)).astype(np.float32),
                  y=rng.normal(size=(32, 4)).astype(np.float32))
    store = device_store(arrays, [np.arange(i, 32, m) for i in range(m)])
    init_fn, sample_fn = make_device_sampler(m, s, b)
    fl = FLConfig(m=m, s=s, strategy="fedawe", flat_state=True)
    rf = make_round_fn(
        fl, lambda tr, fr, bt, k: jnp.mean((bt["x"] @ tr["w"] - bt["y"]) ** 2),
        {}, AvailabilityCfg(kind="sine"), jnp.full((m,), 0.6))
    tr0 = {"w": jnp.ones((4, 4)) * 0.1}
    if not seeds:
        return (make_chunk_fn(fl, rf, sample_fn, K),
                init_fl_state(jax.random.PRNGKey(10), fl, tr0), init_fn,
                store, jax.random.PRNGKey(0), K, sample_fn, rf)
    keys = [jax.random.PRNGKey(i) for i in range(seeds)]
    states = stack_seeds([init_fl_state(jax.random.PRNGKey(10 + i), fl, tr0)
                          for i in range(seeds)])
    sss = stack_seeds([init_fn(store, k) for k in keys])
    return (make_seeds_chunk_fn(fl, rf, sample_fn, K, seeds), states, sss,
            store, jnp.stack(keys), K, sample_fn, rf)


def _traced(tmp_path, drive):
    """Run ``drive`` inside a traced ``bench_window``; the host spans as a
    ``Trace``, and each ``fl_chunk`` span's step number."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        drive()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(traces.find_xplane(str(tmp_path)))
    host, steps = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    host.append(span)
                    if e.name == scopes.CHUNK:
                        steps.append((span[0], dict(e.stats)["step_num"]))
    window = next(h for h in host if h[2] == "bench_window")
    # the CPU has no TPU plane: one stand-in operation lets a Trace hold
    # the host spans
    tr = Trace(window[:2], {0: [(window[0], window[0], "stand-in = add")]},
               {}, host)
    return tr, [n for _, n in sorted(steps)]


def _check_chunk_spans(tr, steps, chunks):
    assert steps == list(range(chunks))
    parts = [scopes.host_spans(tr, n) for n in scopes.SPANS[1:]]
    for i, (s, e) in enumerate(scopes.host_spans(tr, scopes.CHUNK)):
        inside = [[p for p in spans if s <= p[0] and p[1] <= e]
                  for spans in parts]
        assert [len(x) for x in inside] == [1, 1, 1, 1], (i, inside)
        ordered = [x[0] for x in inside]
        # dispatch, fetch, records, hooks, one after the other
        assert all(a[1] <= b[0] for a, b in zip(ordered, ordered[1:]))
    assert len(scopes.host_spans(tr, scopes.CHUNK)) == chunks
    assert chunk_host_ms.read(_run(tr)) > 0


def test_chunk_spans_of_engine_run_rounds(tmp_path):
    from repro.core import run_rounds

    fn, state, init_fn, store, key, K, sample_fn, rf = _problem()
    # the first chunk compiles, outside the trace
    state, _ = run_rounds(state, rf, None, K, chunk_rounds=K, chunk_fn=fn,
                          sample_fn=sample_fn, store=store, data_key=key,
                          sampler_state=init_fn(store, key))
    hooks = []
    tr, steps = _traced(tmp_path, lambda: run_rounds(
        state, rf, None, 3 * K, chunk_rounds=K, chunk_fn=fn,
        sample_fn=sample_fn, store=store, data_key=key,
        sampler_state=init_fn(store, key),
        ckpt_fn=lambda st, t: hooks.append(t), ckpt_every=K))
    assert hooks == [K, 2 * K, 3 * K]
    _check_chunk_spans(tr, steps, 3)


def test_chunk_spans_of_experiments_run_seed_rounds(tmp_path):
    import jax

    from repro.launch.experiments import run_seed_rounds

    fn, states, ss, store, keys, K, _, _ = _problem(seeds=2)
    states, ss, _ = fn(states, ss, store, keys)  # compiles outside the trace
    jax.block_until_ready(states)
    tr, steps = _traced(tmp_path, lambda: run_seed_rounds(
        states, fn, 3 * K, K, sampler_states=ss, store=store,
        data_keys=keys, n_seeds=2))
    _check_chunk_spans(tr, steps, 3)


# -- the names ------------------------------------------------------------------

def _source(*parts):
    with open(os.path.join(SRC, *parts)) as f:
        return f.read()


def test_bench_names_are_the_programs():
    engine = _source("core", "engine.py")
    experiments = _source("launch", "experiments.py")
    scoped = set(re.findall(r'named_scope\("(fl_\w+)"\)', engine))
    assert scoped == set(scopes.SCOPES)
    span = re.compile(r'(?:Step)?TraceAnnotation\(\s*"(fl_\w+)"')
    assert set(span.findall(engine)) == set(scopes.SPANS)
    assert set(span.findall(experiments)) == set(scopes.SPANS)
    # the seed executor's and the packed grid's loops
    assert experiments.count(f'StepTraceAnnotation("{scopes.CHUNK}"') == 2


# -- a trace recorded on the chip ------------------------------------------------

RECORDED = os.path.join(HERE, "testdata", "dense_sine_v5e_scoped.json")


def _recorded():
    with open(RECORDED) as f:
        rec = json.load(f)
    tr = Trace.from_json(rec["trace"])
    return rec, tr, _run(tr, hlo=rec["scopes"], rounds=rec["rounds"],
                         chips=rec["chips"])


def test_recorded_scoped_v5e_trace_readers():
    """Two chunks (16 rounds) of ``cnn100_dense_sine`` traced on one v5e
    chip with the scoped program (``record_trace.py``)."""
    rec, tr, run = _recorded()
    assert rec["rounds"] == 16 and tr.devices == [0]
    for reader in READERS:
        if reader is cohort_move_ms_per_round:
            # dense rounds move no cohort
            with pytest.raises(MissingOp):
                reader.read(run)
            continue
        value = reader.read(run)
        assert math.isfinite(value) and value > 0, reader.__name__
    assert scopes.coverage(tr, rec["scopes"]) >= 0.95


def test_recorded_scoped_v5e_trace_keeps_the_echo_kernel():
    from bench import opnames

    rec, tr, _ = _recorded()
    seconds, calls = tr.op_seconds(opnames.is_echo_kernel,
                                   required="the echo kernel")
    assert calls == 16 and 0 < seconds < 0.01
    # the kernel's name reaches its instruction, under the aggregation
    kernels = {traces.instruction(op) for _, _, op in tr.ops[0]
               if opnames.is_echo_kernel(op)}
    assert len(kernels) == 1
    (kernel,) = kernels
    assert kernel.startswith("fedawe_echo_aggregate")
    assert rec["scopes"][kernel] == scopes.AGGREGATE


def test_recorded_scoped_v5e_trace_shares_one_clock():
    """Each chunk's program starts after its dispatch span starts and ends
    before its fetch span ends."""
    _, tr, _ = _recorded()
    programs = [(s, e) for s, e, name in tr.modules[0]
                if "chunk" in name and tr.window[0] <= 0.5 * (s + e)
                <= tr.window[1]]
    dispatch = scopes.host_spans(tr, scopes.DISPATCH)
    fetch = scopes.host_spans(tr, scopes.FETCH)
    assert len(programs) == len(dispatch) == len(fetch) == 2
    for (ps, pe), (ds, _), (_, fe) in zip(programs, dispatch, fetch):
        assert ds <= ps and pe <= fe
