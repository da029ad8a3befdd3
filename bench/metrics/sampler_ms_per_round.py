"""Device time of the operations under the program's ``fl_sample`` (the
device sampler's draws and, in dense rounds, the ``[m, s*b]`` batch
gather) and ``fl_availability`` (availability, cohort selection) scopes
per round and chip."""
from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.SAMPLE, scopes.AVAILABILITY)
