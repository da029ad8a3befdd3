"""Device time of the operations under the program's ``fl_local_sgd``
scope (every trained row's s local steps: forward, backward, clipping,
update) per round and chip."""
from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.LOCAL_SGD)
