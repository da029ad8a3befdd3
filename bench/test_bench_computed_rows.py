"""``computed_rows_per_trained`` from the window's histories: the rows the
chips ran (each seed at its round's largest counter) over the clients
that trained, and nothing where the program keeps no counter."""
import types

import pytest

from bench.metrics import computed_rows_per_trained


def _run(*histories):
    return types.SimpleNamespace(histories=list(histories))


@pytest.mark.parametrize("histories,want", [
    # dense blocks of 8: 26 and 3 trained rows compute 32 and 8
    (([{"n_active": 26.0, "n_computed": 32.0},
       {"n_active": 3.0, "n_computed": 8.0}],), 40.0 / 29.0),
    # every row trains (all-on, or a cohort whose cap binds)
    (([{"n_active": 100.0, "n_computed": 100.0}],), 1.0),
    # two seeds: each runs the larger seed's rows, round by round
    (([{"n_active": 10.0, "n_computed": 16.0},
       {"n_active": 3.0, "n_computed": 8.0}],
      [{"n_active": 6.0, "n_computed": 8.0},
       {"n_active": 12.0, "n_computed": 16.0}]), 64.0 / 31.0),
    # a program without the counter, or a window where nobody trained
    (([{"n_active": 26.0}],), None),
    (([{"n_active": 0.0, "n_computed": 0.0}],), None),
    ((), None),
])
def test_reads_rows_computed_per_trained_client(histories, want):
    got = computed_rows_per_trained.read(_run(*histories))
    assert got == (None if want is None else pytest.approx(want))
