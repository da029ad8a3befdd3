"""The reference's availability against the program's, for every kind of
``repro.core.availability.KINDS``: over 30 rounds at m = 12 the clients
that train each round, and so every tau, are the same."""
import numpy as np
import pytest
from repro.core.availability import KINDS as PROGRAM_KINDS

from bench import availability, harness, testing

ROUNDS = 30
# every kind of the program, with its module's example fields; and the
# floor, which raises the probabilities and the chain's turn-on rate
KINDS = {k: dict(kind=k, **availability.load(k).EXAMPLE)
         for k in PROGRAM_KINDS}
KINDS["sine_floor"] = dict(kind="interleaved_sine", gamma=0.5, period=10,
                           cutoff=0.2, delta_floor=0.3)
KINDS["markov_floor"] = dict(kind="markov", markov_up=0.1, markov_down=0.5,
                             delta_floor=0.4)


@pytest.fixture
def no_cache(monkeypatch):
    from repro.launch import compilecache
    monkeypatch.setattr(compilecache, "enable", lambda *a, **k: "")


@pytest.mark.parametrize("name", list(KINDS))
def test_reference_counts_equal_the_programs(name, no_cache):
    cell = testing.tiny_cell("cnn100_dense_sine")
    cell.traffic["availability"] = dict(KINDS[name], base_p="from_data")
    cell.traffic["chunk_rounds"] = ROUNDS
    task, prog, first, obs, rows = harness.setup(cell, testing.SEED)
    prog.free()
    (got,) = harness.program_observed(first, obs, rows)
    (want,) = harness.follow(cell, testing.SEED, task, rows)
    assert cell.cfg["deployment"]["m"] == 12
    np.testing.assert_array_equal(got.n_active, want.n_active)
    np.testing.assert_array_equal(got.tau, want.tau)
    # the draws do something: counts move between rounds, some clients
    # are left out and some train
    assert len(set(want.n_active.tolist())) > 1
    assert 0 < want.n_active.sum() < 12 * ROUNDS
