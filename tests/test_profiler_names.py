"""Every layer of a round keeps its ``jax.named_scope`` in the compiled
chunk program: each path's program holds, in its instructions'
``op_name`` metadata, every scope that path runs (the profiler attributes
device time to layers through them)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AvailabilityCfg, FLConfig, init_fl_state,
                        make_chunk_fn, make_round_fn, make_seeds_chunk_fn,
                        stack_seeds)
from repro.core.faults import FaultCfg
from repro.core.staleness import StalenessCfg, init_staleness_state
from repro.data import device_store, make_device_sampler

M, S, B, DIM, K = 6, 2, 4, 4, 2
DENSE = {"fl_sample", "fl_availability", "fl_local_sgd", "fl_aggregate"}
COHORT = DENSE | {"fl_cohort_gather", "fl_cohort_scatter"}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _loss_fn(tr, frozen, batch, rng):
    return 0.5 * jnp.mean((batch["x"] @ tr["w"] - batch["y"]) ** 2)


def _compiled_scopes(path):
    """The ``fl_*`` names in the compiled chunk program's metadata."""
    rng = np.random.default_rng(0)
    arrays = dict(x=rng.normal(size=(48, DIM)).astype(np.float32),
                  y=rng.normal(size=(48, DIM)).astype(np.float32))
    store = device_store(arrays, [np.arange(i, 48, M) for i in range(M)])
    cohort = path == "cohort"
    init_fn, sample_fn = make_device_sampler(
        M, S, B, emit="cols" if cohort else "batches")
    fl = FLConfig(m=M, s=S, strategy="fedawe", flat_state=path != "tree",
                  sparse_cohort=3 if cohort else 0,
                  resident_dtype="bfloat16" if cohort else "float32")
    fault = stale_cfg = stale = None
    if path == "stale_faults":
        fault = FaultCfg(upload_survival=0.8, sanitize=True)
        stale_cfg = StalenessCfg(tau_max=2, kind="det", delay=1)
        stale = init_staleness_state(stale_cfg, DIM * DIM, M)
    rf = make_round_fn(fl, _loss_fn, {}, AvailabilityCfg(kind="sine"),
                       jnp.full((M,), 0.6), fault_cfg=fault,
                       staleness_cfg=stale_cfg)
    tr0 = {"w": jnp.ones((DIM, DIM)) * 0.1}
    dk = jax.random.PRNGKey(1)
    state = init_fl_state(jax.random.PRNGKey(0), fl, tr0, stale=stale)
    ss = init_fn(store, dk)
    if path == "seeds":
        states = stack_seeds([state, state])
        sss = stack_seeds([ss, ss])
        keys = jnp.stack([dk, jax.random.PRNGKey(2)])
        fn = make_seeds_chunk_fn(fl, rf, sample_fn, K, 2)
        lowered = fn.lower(states, sss, store, keys)
    else:
        lowered = make_chunk_fn(fl, rf, sample_fn, K).lower(state, ss,
                                                            store, dk)
    text = lowered.compile().as_text()
    return {part for name in _OP_NAME.findall(text)
            for part in re.findall(r"\bfl_\w+", name)}


@pytest.mark.parametrize("path,want", [
    ("dense", DENSE), ("cohort", COHORT), ("seeds", DENSE),
    ("tree", DENSE), ("stale_faults", DENSE)])
def test_compiled_chunk_program_keeps_every_scope(path, want):
    assert _compiled_scopes(path) == want
