"""Mean idle time between one execution of the chunk program and the next
on a chip: what the device waits for the host's per-chunk metrics fetch,
record building and next dispatch."""
from bench.traces import MissingOp


def read(run):
    gaps = run.trace.program_gaps(lambda name: "chunk" in name)
    if not gaps:
        raise MissingOp("no two consecutive chunk programs in the trace")
    return 1e3 * sum(gaps) / len(gaps)
