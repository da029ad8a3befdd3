"""Device time of the operations under the program's ``fl_aggregate``
scope (the innovations, upload masks, the strategy's aggregation with the
echo kernel and its pads, the client and tau updates) per round and
chip."""
from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.AGGREGATE)
