"""Trace a few chunks of one cell and keep what the readers of the
program's scopes and spans need.

    python3 bench/record_trace.py --workload <cell> --seed <n> \\
        --chunks <c> --out <file.json>

Set-up is a run's (``harness.setup``, then one chunk more); the window is
``c`` chunks through the program's executor inside a ``bench_window``
span, traced.  No check runs.  ``<file.json>`` holds the window's trace
(``trace``: operation names compacted, of the host spans only the window
and the program's ``fl_*`` spans), the scope of every instruction the
trace ran (``scopes``), ``rounds`` per seed and ``chips``.  The last line
of standard output is one JSON object: each per-layer reading of the
program's names, the share of the chips' busy time under some scope, and
the unscoped operations that took most device time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

READERS = ("local_sgd_ms_per_round", "aggregate_ms_per_round",
           "sampler_ms_per_round", "cohort_move_ms_per_round",
           "chunk_host_ms", "chunk_gap_ms")


def record(cell, seed: int, chunks: int) -> dict:
    """Set-up, ``chunks`` traced chunks, and what the file holds."""
    import jax

    from bench import harness, scopes, traces

    _, prog, _, _, _ = harness.setup(cell, seed)
    prog.run(prog.K)
    directory = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(directory)
    with jax.profiler.TraceAnnotation("bench_window"):
        prog.run(prog.K * chunks)
        jax.block_until_ready(prog.state)
    jax.profiler.stop_trace()
    tr = traces.load_dir(directory)
    traces.remove_dir(directory)
    smap = scopes.instruction_scopes(prog.chunk.as_text())
    ran = {traces.instruction(op) for ops in tr.ops.values()
           for _, _, op in ops}
    keep = set(scopes.SPANS) | {"bench_window"}
    tr.host = [h for h in tr.host if h[2] in keep]
    return dict(trace=tr.to_json(),
                scopes={k: v for k, v in smap.items() if k in ran},
                rounds=prog.K * chunks, chips=cell.chips)


def readings(cell, rec: dict) -> dict:
    """The readers of ``READERS`` that apply to the cell, the scoped
    share of busy time, and the top unscoped operations."""
    import importlib

    from bench import harness, scopes, traces
    from bench.traces import MissingOp

    tr = traces.Trace.from_json(rec["trace"])
    run = harness.RunInfo(
        cell=cell, rounds=rec["rounds"], seeds=1, chips=rec["chips"],
        window_s=tr.window_s(), histories=[], chunk_stamps=[], memory={},
        hlo=rec["scopes"], peaks=None, trace=tr)
    listed = {p["name"] for p in cell.per_layer}
    out = {}
    for name in READERS:
        if name in listed:
            try:
                out[name] = importlib.import_module(
                    f"bench.metrics.{name}").read(run)
            except MissingOp as e:
                out[name] = f"MissingOp: {e}"
    out["scoped_share"] = 100.0 * scopes.coverage(tr, rec["scopes"])
    out["scope_ms_per_round"] = {
        s: 1e3 * scopes.seconds(tr, rec["scopes"], [s]) / (
            rec["rounds"] * rec["chips"])
        for s in scopes.SCOPES if s in rec["scopes"].values()}
    out["unscoped"] = scopes.unscoped(tr, rec["scopes"])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chunks", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    from repro.launch import compilecache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    cell = harness.load_cell(args.workload)
    if len(jax.devices()) < cell.chips:
        raise SystemExit(f"record_trace: {args.workload} needs "
                         f"{cell.chips} chips")
    compilecache.enable()
    rec = record(cell, args.seed, args.chunks)
    with open(args.out, "w") as f:
        json.dump(rec, f)
    print(json.dumps(readings(cell, rec)), flush=True)


if __name__ == "__main__":
    main()
