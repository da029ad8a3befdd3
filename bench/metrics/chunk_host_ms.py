"""Mean host time at a chunk boundary of the window, from the end of one
chunk's ``fl_chunk_fetch`` span (its metrics are on the host) to the end
of the next chunk's ``fl_chunk_dispatch`` span: building the records, the
hooks, and the next dispatch."""
from bench import scopes


def read(run):
    gaps = scopes.chunk_host_gaps(run.trace)
    return None if gaps is None else 1e3 * sum(gaps) / len(gaps)
