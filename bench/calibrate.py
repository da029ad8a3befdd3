"""Readings that the limits of a cell's check are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --program-seeds 1,2,... \\
        --control-seeds 21,22,23 [--fault-seeds 21,22,23] [--witness-seeds 1]

For each program seed: the run's set-up (inputs from the seed, the
program built, its first chunk through the window's own executor) and the
reference's follow of that chunk, compared as a run compares them; one
line per seed replicate, and one with both sides' per-round losses.  For
each witness seed (one of the program seeds), also the reference at the
program's own default matrix precision, compared with the program.

For each control seed: the reference computed one precision below the
configuration's (bf16 for f32) put in the program's place.  For each
fault seed: the reference with half of every batch left out, the mean
taken over the rest, in the program's place.  Neither needs the program,
so they run on one chip for any cell.  The benchmark's own runs never run
the control or the faults.  Program seeds need the chips the cell asks
for.  Prints one JSON object a line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--witness-seeds", type=_seeds, default=[],
                    help="program seeds also compared with the reference "
                         "at the program's default matrix precision")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness, traffic
    from repro.launch import compilecache

    compilecache.enable()
    cell = harness.load_cell(args.workload)

    def emit(kind, seed, j, numbers):
        print(json.dumps(dict(kind=kind, seed=seed, replicate=j,
                              **{k: float(v) for k, v in numbers.items()})),
              flush=True)

    for seed in args.program_seeds:
        task, prog, first, obs, row_ids = harness.setup(cell, seed)
        chips = prog.seed_chips() if prog.mesh else None
        prog.free()
        got = harness.program_observed(first, obs, row_ids)
        want = harness.follow(cell, seed, task, row_ids)
        for j, (g, w) in enumerate(zip(got, want)):
            numbers = harness.compare(cell, task, [g], [w])
            if chips is not None:
                numbers["placement_mismatch"] = prog.seeds - chips
            emit("program", seed, j, numbers)
            print(json.dumps(dict(kind="losses", seed=seed, replicate=j,
                                  program=g.loss.tolist(),
                                  reference=w.loss.tolist())), flush=True)
        if seed in args.witness_seeds:
            # the reference at the program's own matrix precision
            wit = harness.follow(cell, seed, task, row_ids,
                                 precision="default")
            for j, (g, w) in enumerate(zip(got, wit)):
                emit("witness", seed, j, harness.compare(cell, task, [g], [w]))
                print(json.dumps(dict(kind="witness_losses", seed=seed,
                                      replicate=j, reference=w.loss.tolist())),
                      flush=True)

    for kind, seeds, variant in (
            ("control", args.control_seeds, dict(dtype="bfloat16")),
            ("half_batch", args.fault_seeds, dict(half_batch=True))):
        for seed in seeds:
            task = traffic.make_task(cell.cfg, cell.traffic, seed)
            task.free_arrays()
            row_ids = harness.sample_rows(cell, seed)
            want = harness.follow(cell, seed, task, row_ids)
            got = harness.follow(cell, seed, task, row_ids, **variant)
            for j, (g, w) in enumerate(zip(got, want)):
                emit(kind, seed, j, harness.compare(cell, task, [g], [w]))


if __name__ == "__main__":
    main()
