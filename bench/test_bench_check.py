"""The check that decides ``correct``, driven through the rest of a run at a
small size on the CPU: a sound run passes, the control (the reference one
precision below the configuration, in the program's place) and every
planted fault of the timed path fail."""
import pytest

from bench import harness, testing

ONE_CHIP = [w["name"] for w in harness.load_benchmark()["workloads"]
            if w["chips"] == 1]


def _every_echo_is_one(name):
    """Full participation: every client trains every round, so each echo
    t - tau_i is 1 and dropping it changes nothing."""
    av = harness.load_cell(name).traffic["availability"]
    return av["kind"] == "stationary" and av["base_p"] == "ones"


@pytest.fixture
def no_cache(monkeypatch):
    from repro.launch import compilecache
    monkeypatch.setattr(compilecache, "enable", lambda *a, **k: "")


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name, no_cache):
    res = testing.run_tiny(testing.tiny_cell(name))
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"rounds_per_s", "hbm_peak_gb", "setup_s"}


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name):
    cell = testing.tiny_cell(name)
    task = harness.traffic_mod.make_task(cell.cfg, cell.traffic,
                                         testing.SEED)
    rows = harness.sample_rows(cell, testing.SEED)
    want = harness.follow(cell, testing.SEED, task, rows)
    got = harness.follow(cell, testing.SEED, task, rows, dtype="bfloat16")
    ok, shown = harness.verdict(harness.compare(cell, task, got, want),
                                cell.limits)
    assert not ok, shown


@pytest.mark.parametrize("name,fault", [
    (c, f) for c in ONE_CHIP
    for f in ("state_unchanged", "half_batch", "echo_dropped")
    if not (f == "echo_dropped" and _every_echo_is_one(c))])
def test_fault_is_not_correct(name, fault, no_cache, monkeypatch):
    testing.FAULTS[fault](monkeypatch)
    res = testing.run_tiny(testing.tiny_cell(name))
    assert not res["correct"], res["check"]
