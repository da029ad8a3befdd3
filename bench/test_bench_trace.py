"""The reduction from a trace to per-layer numbers: on hand-made traces, on
a trace recorded here on the CPU, and on a trace recorded on a v5e chip."""
import os

import pytest

from bench import opnames, traces
from bench.traces import MissingOp, Trace, compact_name, opcode

HERE = os.path.dirname(os.path.abspath(__file__))


def _trace():
    # two chips over a 10 s window; chip 1 idles more
    ops = {0: [(1.0, 2.0, "fusion.1 = fusion kind=kLoop"),
               (1.5, 3.0, "convolution.2 = convolution"),
               (5.0, 6.0, "convolution.3 = convolution"),
               (1.0, 6.0, "while.9 = while"),
               (9.8, 11.0, "fusion.4 = fusion kind=kLoop")],  # clipped at 10
           1: [(2.0, 3.0, "all-reduce.5 = all-reduce"),
               (-1.0, 0.5, "fusion.6 = fusion")]}             # clipped at 0
    modules = {0: [(0.5, 3.0, "jit_chunk(7)"), (5.0, 6.0, "jit_chunk(7)"),
                   (9.5, 12.0, "jit_chunk(7)")],
               1: [(2.0, 3.0, "jit_chunk(7)")]}
    host = [(0.0, 10.0, "bench_window"), (3.0, 5.0, "device_get"),
            (3.5, 4.5, "dispatch")]
    return Trace((0.0, 10.0), ops, modules, host)


def test_busy_union_and_idle_share():
    tr = _trace()
    # chip 0: [1, 6] + [9.8, 10] = 5.2 s; chip 1: 1 + 0.5 = 1.5 s
    assert tr.busy_s() == pytest.approx((5.2 + 1.5) / 2)
    assert tr.idle_share() == pytest.approx(1 - 3.35 / 10)
    assert tr.window_s() == 10.0


def test_gaps_and_what_the_host_did():
    tr = _trace()
    assert tr.gaps(0) == [(0.0, 1.0), (6.0, 9.8)]
    assert tr.host_activity(3.0, 5.0) == "dispatch"
    assert tr.host_activity(6.0, 9.8) == "no host span"
    gaps = tr.breakdown()["idle_gaps"]
    assert gaps[0] == ["no host span", pytest.approx(7.0)]  # chip 1, 3 to 10
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_program_gaps_inside_the_window():
    # an event belongs to the window when its middle does: the first chunk
    # starts before the host span, the third ends after it and is left out
    assert _trace().program_gaps(lambda n: "chunk" in n) == [2.0]


def test_op_seconds_by_category_and_missing_name():
    tr = _trace()
    total, n = tr.op_seconds(lambda name: opcode(name) == "convolution",
                             required="a convolution")
    assert (total, n) == (pytest.approx(2.5), 2)
    with pytest.raises(MissingOp, match="the echo kernel"):
        tr.op_seconds(opnames.is_echo_kernel, required="the echo kernel")
    assert tr.op_seconds(opnames.is_echo_kernel) == (0.0, 0)
    assert tr.op_seconds(opnames.is_collective) == (pytest.approx(1.0), 1)


def test_breakdown_ranks_ops_by_time_without_loops():
    ops = _trace().breakdown()["device_ops"]
    assert ops[0] == ["convolution.2 = convolution", pytest.approx(0.75)]
    assert all(not name.startswith("while") for name, _ in ops)
    assert len(ops) <= 10


def test_json_round_trip(tmp_path):
    tr = _trace()
    path = str(tmp_path / "t.json")
    traces.save_json(tr, path)
    back = traces.load_json(path)
    assert back.busy_s() == tr.busy_s() and back.window == tr.window


def test_no_device_operation_is_refused():
    with pytest.raises(MissingOp, match="no device operation"):
        Trace((0.0, 1.0), {0: []}, {}, [])


def test_xplane_of_a_cpu_run(tmp_path):
    """The loader finds the window span in a real ``.xplane.pb``; a CPU run
    has no TPU plane, so it refuses the trace."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = traces.find_xplane(str(tmp_path))
    with pytest.raises(MissingOp, match="no device operation"):
        traces.load(path)
    with pytest.raises(MissingOp, match="'other_window' host spans"):
        traces.load(path, window_name="other_window")


def test_compact_names_of_hlo_instructions():
    assert compact_name(
        '%closed_call.30 = f32[1,274432]{1,0:T(1,128)S(1)} custom-call('
        'f32[1,2]{1,0:T(1,128)S(1)} %maximum_bitcast_fusion.6), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[1,2]{1,0}}') == \
        "closed_call.30 = custom-call custom_call_target=tpu_custom_call"
    assert compact_name(
        "%bitcast_or_fusion.2 = (s32[100,160,1]{0,1,2:T(8,128)S(1)}, "
        "s32[100,160]{0,1:T(8,128)S(1)}) fusion(u32[]{:T(128)S(6)} "
        "%xor.2471), kind=kLoop, calls=%fused_computation.122.clone") == \
        "bitcast_or_fusion.2 = fusion kind=kLoop " \
        "calls=%fused_computation.122.clone"
    assert opcode("while.96 = while") == "while"
    assert compact_name("not an instruction") == "not an instruction"


def test_recorded_v5e_trace():
    """Two chunks (16 rounds) of ``cnn100_dense_sine`` traced on one v5e
    chip (names compacted, the Python tracer's host spans dropped)."""
    tr = traces.load_json(os.path.join(HERE, "testdata",
                                       "dense_sine_v5e_trace.json"))
    assert tr.devices == [0]
    assert 0 < tr.busy_s() <= tr.window_s()
    assert 0 <= tr.idle_share() < 0.05
    gaps = tr.program_gaps(lambda name: "chunk" in name)
    assert len(gaps) == 1 and 0 < gaps[0] < 0.1
    seconds, calls = tr.op_seconds(opnames.is_echo_kernel,
                                   required="the echo kernel")
    assert calls == 16 and 0 < seconds < 0.01
    assert tr.op_seconds(opnames.is_collective) == (0.0, 0)
    ops = tr.breakdown()["device_ops"]
    assert len(ops) == 10
    assert sum(v for _, v in ops) < tr.busy_s()
