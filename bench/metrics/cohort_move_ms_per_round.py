"""Device time of the operations under the program's
``fl_cohort_gather`` (the cohort's batches and its rows of the resident
stack, promoted to f32) and ``fl_cohort_scatter`` (the rows written back,
demoted) scopes per round and chip."""
from bench import scopes


def read(run):
    return scopes.ms_per_round(run, scopes.COHORT_GATHER,
                               scopes.COHORT_SCATTER)
