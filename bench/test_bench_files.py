"""BENCHMARK.json against the benchmark's contract, and every file a cell
is found by."""
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "channels", "experts_per_tok")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_command_and_paths():
    cmd, paths = BENCH["command"], BENCH["paths"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for w in cmd[1:]:
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in paths), w


def test_run_seconds_fits_a_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_unique():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_configs():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    assert 1 <= len(BENCH["configs"]) <= 24
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"]) and c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS), key
            assert key in body["reduced_from"]


def test_workloads():
    assert 1 <= len(BENCH["workloads"]) <= 24
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)
    for cell in CELLS:
        reported = [m for m in BENCH["end_to_end"]
                    if cell in m.get("workloads", CELLS)]
        assert len(reported) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load(name):
    cell = harness.load_cell(name)
    dep = cell.cfg["deployment"]
    # the seed mesh holds one seed a chip; every other cell runs one seed
    assert dep["seeds"] == (cell.chips if dep["mesh"] == "seed" else 1)
    assert cell.traffic["chunk_rounds"] >= 1
    assert cell.limits["count_mismatch"] == 0
    gaps = [cell.limits[k] for k in ("loss_gap", "global_gap", "client_gap")]
    # null: the number is not compared in this cell (PERF.md says why)
    assert all(g is None or 0 < g < 1 for g in gaps)
    assert sum(g is not None for g in gaps) >= 2
    if dep["mesh"] == "seed":
        assert cell.limits["placement_mismatch"] == 0


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("stats,want", [
    # a mesh chip after the window (TPU v5 lite): 1.96 GB of buffers at
    # their peak beside 7.75 GB reserved for the chunk program's temporaries
    (dict(peak_bytes_in_use=1958759424, peak_bytes_reserved=7748468736,
          bytes_in_use=747517952, bytes_reserved=7748468736), 9707228160),
    (dict(peak_bytes_in_use=5, peak_bytes_reserved=0), 5),
])
def test_hbm_peak_counts_reserved_temporaries(stats, want):
    assert harness.hbm_peak_bytes(stats) == want


def test_hbm_peak_needs_both_allocator_peaks():
    with pytest.raises(KeyError):
        harness.hbm_peak_bytes(dict(peak_bytes_in_use=5))
