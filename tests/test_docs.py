"""Docs can't rot silently: the fenced shell commands in README.md are
extracted and (for the cheap ``--help`` ones, plus the mini dry-run as a
slow test) actually executed, and every ``--flag`` the README shows for a
CLI must exist in that CLI's argparse ``--help`` output."""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO, "README.md")

_FENCE = re.compile(r"```(?:bash|sh|shell)\n(.*?)```", re.S)


def _shell_commands():
    """Fenced shell commands from README.md, with line continuations
    joined: one string per command."""
    text = open(README).read()
    cmds = []
    for block in _FENCE.findall(text):
        block = block.replace("\\\n", " ")
        for line in block.splitlines():
            line = line.strip()
            if line and not line.startswith("#"):
                cmds.append(line)
    assert cmds, "README.md has no fenced shell commands"
    return cmds


def _run(cmd, timeout=600):
    """Run one README command from the repo root, PYTHONPATH=src wired
    (the README exports it once; each subprocess needs it in env)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p)
    # honour inline VAR=val prefixes (e.g. REPRO_DRYRUN_DEVICES=4)
    parts = cmd.split()
    while parts and "=" in parts[0] and not parts[0].startswith(("python",)):
        k, v = parts.pop(0).split("=", 1)
        env[k] = v
    parts = [sys.executable if p == "python" else p for p in parts]
    return subprocess.run(parts, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=timeout)


def test_readme_has_quickstart_and_pointers():
    text = open(README).read()
    assert "python -m pytest -x -q" in text, "tier-1 command missing"
    for pointer in ("docs/ARCHITECTURE.md", "docs/BENCHMARKS.md",
                    "BENCH_kernels.json", "repro.launch.train",
                    "repro.launch.experiments", "repro.launch.dryrun",
                    "--scenario", "--seeds"):
        assert pointer in text, f"README lost its {pointer} pointer"
    for doc in ("docs/ARCHITECTURE.md", "docs/BENCHMARKS.md"):
        assert os.path.exists(os.path.join(REPO, doc)), doc


def test_readme_help_commands_run():
    """Every ``--help`` command in the README exits 0 and prints usage."""
    helps = [c for c in _shell_commands() if "--help" in c]
    assert len(helps) >= 3, "README should show --help for the main CLIs"
    for cmd in helps:
        r = _run(cmd, timeout=300)
        assert r.returncode == 0, f"{cmd!r} failed:\n{r.stderr[-2000:]}"
        assert "usage:" in r.stdout


def _help_for(module_cmd):
    r = _run(f"python -m {module_cmd} --help", timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_readme_flags_exist_in_argparse():
    """Each ``--flag`` the README passes to a CLI module must be defined
    by that module's argparse (checked against its --help text, which
    argparse generates from the real parser) — a renamed or removed flag
    fails here before a user hits it."""
    helps = {}
    missing = []
    for cmd in _shell_commands():
        m = re.search(r"-m\s+(repro\.launch\.\w+)", cmd)
        if m:
            mod = m.group(1)
        elif "tools/bench_record.py" in cmd:
            mod = "tools/bench_record.py"
        else:
            continue
        if mod not in helps:
            helps[mod] = (_help_for(mod) if mod.startswith("repro.")
                          else _run(f"python {mod} --help").stdout)
        for flag in re.findall(r"(--[A-Za-z][A-Za-z0-9-]*)", cmd):
            if flag == "--help":
                continue
            if flag not in helps[mod]:
                missing.append((mod, flag, cmd))
    assert not missing, f"README references undefined flags: {missing}"


def test_readme_scenario_names_registered():
    """Scenario / grid names the README mentions must exist in the
    experiments registry."""
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.launch.experiments import GRIDS, SCENARIOS
    finally:
        sys.path.pop(0)
    text = open(README).read()
    for name in re.findall(r"--scenario\s+([\w/@+.-]+)", text):
        assert name in SCENARIOS, f"README --scenario {name} unregistered"
    for name in re.findall(r"--grid\s+([\w-]+)", text):
        assert name in GRIDS, f"README --grid {name} unregistered"


def test_readme_list_command_runs():
    """The README's cheap, side-effect-free experiments command
    (``--list``) actually executes and prints registered cells/grids."""
    cmds = [c for c in _shell_commands() if "--list" in c]
    assert cmds, "README lost its --list quickstart command"
    r = _run(cmds[0], timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "fedawe/sine" in r.stdout and "grid speedup-sine" in r.stdout


def test_readme_bench_dry_gate_runs():
    """The README's ``--check --dry`` schema gate executes against the
    committed BENCH_kernels.json (no measurement, CI-safe)."""
    cmds = [c for c in _shell_commands() if "--dry" in c]
    assert cmds, "README lost its bench --check --dry command"
    r = _run(cmds[0], timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "schema gate OK" in r.stdout


def test_readme_shows_seed_axis_flags():
    """The seed-axis production features stay documented: the README must
    keep showing --packed / --replicate and the +mesh dry-run variant."""
    text = open(README).read()
    for needle in ("--packed", "--replicate", "seeds4+mesh",
                   "chunked_seeds_mesh", "--check --dry"):
        assert needle in text, f"README lost {needle}"


def test_readme_shows_packed_mesh_and_compile_cache():
    """The whole-grid single-program features stay documented: the
    README must keep the --packed --seed-mesh composition quickstart,
    the bucket-padding opt-out, the persistent-compilation-cache flag,
    and the new bench row families; BENCHMARKS.md must keep their
    glossary rows and the cache-placement/CI-restore semantics."""
    text = open(README).read()
    for needle in ("--packed --seed-mesh", "--compile-cache",
                   "--no-pad-buckets", "--grid paper-sec7",
                   "compile_time_s/", "dispatch_count/"):
        assert needle in text, f"README lost {needle}"
    bench = open(os.path.join(REPO, "docs", "BENCHMARKS.md")).read()
    for needle in ("compile_count/<exec>", "dispatch_count/<exec>",
                   "compile_time_s/<exec>", "Persistent compilation cache",
                   "JAX_COMPILATION_CACHE_DIR", ".jax_cache",
                   "actions/cache", "--compile-cache"):
        assert needle in bench, f"BENCHMARKS.md lost {needle}"


def test_readme_shows_chip_runs():
    """How the program runs on the chip stays documented: the README's
    chip commands, the CPU rehearsal, and the compile tests it names;
    every flag a chip command passes exists in chip_smoke.py."""
    text = open(README).read()
    for needle in ("python chip_smoke.py", "--four-chips",
                   "JAX_PLATFORMS=cpu", "xla_force_host_platform_device_count",
                   "tests/test_tpu_compile.py", "tpu_custom_call"):
        assert needle in text, f"README lost {needle}"
    chip_cmds = [c for c in _shell_commands() if "chip_smoke.py" in c]
    assert chip_cmds, "README lost its chip_smoke.py command"
    smoke = open(os.path.join(REPO, "chip_smoke.py")).read()
    for cmd in chip_cmds:
        for flag in re.findall(r"(--[A-Za-z][A-Za-z0-9-]*)", cmd):
            assert f'"{flag}"' in smoke, f"chip_smoke.py lacks {flag}"
    for doc in ("tests/test_tpu_compile.py", "chip_smoke.py"):
        assert os.path.exists(os.path.join(REPO, doc)), doc


def test_readme_shows_semi_async_quickstart():
    """The semi-async substrate stays documented: the README must keep
    the staleness train flags, the +staleness dry-run variant, the
    staleness grid, and the FedAR baseline cell."""
    text = open(README).read()
    for needle in ("--stale-max", "--stale-kind", "--stale-gamma",
                   "flat_chunk4+staleness", "--grid staleness",
                   "fedar/semi_async", "chunked_staleness"):
        assert needle in text, f"README lost {needle}"


def test_readme_shows_sparse_cohort_quickstart():
    """The sparse cohort substrate stays documented: the README must keep
    the cohort train flags, the parity-harness pointer, and the bench
    rows; ARCHITECTURE.md must keep its Sparse cohort rounds section."""
    text = open(README).read()
    for needle in ("--sparse-cohort", "--resident-dtype",
                   "tests/test_sparse_cohort.py",
                   "rounds_per_sec/sparse_cohort",
                   "resident_bytes/sparse_cohort"):
        assert needle in text, f"README lost {needle}"
    arch = open(os.path.join(REPO, "docs", "ARCHITECTURE.md")).read()
    for needle in ("Sparse cohort rounds", "cohort_select",
                   "cohort_gather", "cohort_scatter", "n_deferred",
                   "emit=\"cols\"", "cohort_pspecs",
                   "resident_bytes/sparse_cohort"):
        assert needle in arch, f"ARCHITECTURE.md lost {needle}"


def test_readme_flcheck_quickstart_runs_clean():
    """The README's static-invariants quickstart (`python -m tools.flcheck
    src/`) is a real fenced command AND exits 0 against the committed
    tree — a violation that lands in src/ fails the docs suite too."""
    cmds = [c for c in _shell_commands() if "tools.flcheck" in c]
    assert cmds, "README lost its flcheck quickstart command"
    r = _run(cmds[0], timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "clean" in r.stdout


def test_docs_cover_static_invariants():
    """The invariant docs stay in place: ARCHITECTURE.md's rule table and
    the README's pragma contract."""
    text = open(README).read()
    for needle in ("python -m tools.flcheck src/", "flcheck: ignore[",
                   "Static invariants"):
        assert needle in text, f"README lost {needle}"
    arch = open(os.path.join(REPO, "docs", "ARCHITECTURE.md")).read()
    for needle in ("Invariants & static checks", "no-host-sync-in-jit",
                   "key-hygiene", "donation-discipline", "registry-contract",
                   "nan-confinement", "compile_count", "strict_rails",
                   'transfer_guard("disallow")'):
        assert needle in arch, f"ARCHITECTURE.md lost {needle}"


@pytest.mark.slow
def test_readme_dryrun_command_runs(tmp_path):
    """Smoke-run the README's mini dry-run command (rewritten to a tmp
    output path so the committed results/ file is untouched)."""
    cmds = [c for c in _shell_commands()
            if "repro.launch.dryrun" in c and "--help" not in c]
    assert cmds, "README lost its dry-run quickstart command"
    cmd = cmds[0]
    assert "REPRO_DRYRUN_DEVICES" in cmd, \
        "README dry-run must pin REPRO_DRYRUN_DEVICES for laptop/CI use"
    out = tmp_path / "dry.json"
    cmd = re.sub(r"--out\s+\S+", f"--out {out}", cmd)
    r = _run(cmd, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.load(open(out))[0]
    assert rec["ok"], rec
