"""Compiles for a TPU v5e chip that is described, not attached.

The TPU compiler is installed beside the CPU backend, so the Mosaic echo
kernel and the main chunk program can be compiled for one v5e chip here.
That catches what interpret mode cannot: blocks the Mosaic layout rules
refuse, kernels that overflow VMEM, programs that overflow HBM.  Nothing
runs, so nothing here says anything about results or time.

The topology is described inside a fixture: only one process at a time
may load the TPU library, and describing it at import would make the
test workers collect different tests.  The persistent compilation cache
is turned off around these compiles (a TPU executable cannot be read back
without a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.echo_aggregate.kernel import echo_aggregate_fused_pallas

N_CNN = 273_706          # the paper's CNN at 32x32x3 (models/cnn.py)
HBM_BYTES = 16e9         # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import \
        compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("m,upload,seeds", [
    (100, False, 0), (100, True, 0),
    # an [m, 4096] f32 tile per stack overflowed VMEM from m = 256 on
    (256, False, 0), (1000, False, 0),
    # vmap over seeds adds a leading axis to every block
    (100, False, 4),
])
def test_echo_kernel_compiles_for_v5e(one_chip, m, upload, seeds):
    lead = (seeds,) if seeds else ()
    args = [_sds(one_chip, lead + (m, N_CNN)), _sds(one_chip, lead + (m, N_CNN)),
            _sds(one_chip, lead + (N_CNN,)), _sds(one_chip, lead + (m,)),
            _sds(one_chip, lead + (m,))]
    if upload:
        args.append(_sds(one_chip, lead + (m,)))

    def agg(x, y, g, mask, echo, up=None):
        return echo_aggregate_fused_pallas(x, y, g, mask, echo, 1.0,
                                           interpret=False, upload=up)

    fn = jax.vmap(agg) if seeds else agg
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cnn_chunk_program_fits_one_chip(one_chip, monkeypatch):
    """The chunk program of chip_smoke's phase (b): the CNN at 32x32x3,
    m = 100, s = 5, batch 32, K = 8, lowered from shapes."""
    from repro.core import (AvailabilityCfg, FLConfig, init_fl_state,
                            make_chunk_fn, make_round_fn)
    from repro.data import make_device_sampler
    from repro.kernels.echo_aggregate import ops
    from repro.models import cnn

    # the CPU backend answers the platform query; the compile is for TPU
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    m, s, b, n, K = 100, 5, 32, 65_536, 8
    params = cnn.init_cnn(jax.random.PRNGKey(0), in_shape=(32, 32, 3))
    fl = FLConfig(m=m, s=s, strategy="fedawe", flat_state=True,
                  use_kernel=True)
    rf = make_round_fn(fl, cnn.make_image_loss_fn(cnn.cnn_apply), {},
                       AvailabilityCfg(kind="sine", gamma=0.3),
                       jnp.full((m,), 0.5))
    _, sample_fn = make_device_sampler(m, s, b)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    state = on_chip(jax.eval_shape(lambda k: init_fl_state(k, fl, params),
                                   jax.random.PRNGKey(0)))
    assert state.global_tr.shape == (N_CNN,)
    store = on_chip(dict(
        arrays=dict(images=jax.ShapeDtypeStruct((n, 32, 32, 3), jnp.float32),
                    labels=jax.ShapeDtypeStruct((n,), jnp.int32)),
        idx=jax.ShapeDtypeStruct((m, 2 * n // m), jnp.int32),
        counts=jax.ShapeDtypeStruct((m,), jnp.int32)))
    key = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    compiled = make_chunk_fn(fl, rf, sample_fn, K).lower(
        state, {}, store, key).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total
