"""The seed-mesh cell's check at a small size: it needs four devices, so a
child process gets four virtual CPU devices and runs the cell sound and
with each planted fault, the seeds collapsed onto one chip among them."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_seed_mesh_cell_on_four_devices():
    """The seed-mesh cell needs four devices: a child process gets four
    virtual CPU devices and runs it sound and with each fault."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run(
        [sys.executable, "-m", "bench.testing", "cnn100_seedmesh4_sine",
         "state_unchanged", "half_batch", "one_chip_mesh"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    runs = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    assert [r["fault"] for r in runs] == [None, "state_unchanged",
                                          "half_batch", "one_chip_mesh"]
    assert runs[0]["correct"], runs[0]["check"]
    for r in runs[1:]:
        assert not r["correct"], r
    assert runs[3]["check"]["placement_mismatch"]["value"] > 0
