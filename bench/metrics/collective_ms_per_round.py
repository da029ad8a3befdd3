"""Device time of collective operations (all-reduce, all-gather,
all-to-all, collective-permute, reduce-scatter) per round and chip.
Independent seeds need none beyond gathering their metrics, so time here
is placement at fault."""
from bench import opnames


def read(run):
    total, _ = run.trace.op_seconds(
        lambda name: opnames.is_collective(name))
    return 1e3 * total / (run.rounds * run.chips)
