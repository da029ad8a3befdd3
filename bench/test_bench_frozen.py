"""A model kind that no file of the benchmark names, with a frozen base:
this file registers itself as ``bench.models.lora_lm_test`` and a cell of
it runs set-up, window and check through the harness unchanged.

The kind is the repo's ``tiny`` language model with LoRA adapters
(``fl_mode="lora"``): the adapters train, the rest is the frozen base,
which reaches the chunk program as a runtime argument.  Its reference loss
calls the program's own ``lm_loss``, so this tests the plumbing (the
frozen path, the generic task, store, reference and check), not the
model's mathematics."""
import dataclasses
import sys

import numpy as np
import pytest

from bench import harness, testing

KIND = "lora_lm_test"
CLIENT_BLOCK = 4


def _lm():
    from repro.configs.tiny import CONFIG

    return dataclasses.replace(CONFIG, fl_mode="lora", lora_rank=4)


# -- the model module's interface (bench/models/__init__.py) ------------------

def groups(rng, model, n):
    return rng.integers(0, model["n_groups"], n).astype(np.int32)


def arrays(key, groups, model, data):
    """Token sequences from a per-group distribution over the vocabulary;
    the labels are the next tokens."""
    import jax
    import jax.numpy as jnp

    V, L = _lm().vocab, model["seq_len"]
    kp, kt = jax.random.split(key)
    logits = 3.0 * jax.random.normal(kp, (model["n_groups"], V))
    seq = jax.random.categorical(kt, logits[jnp.asarray(groups)][:, None],
                                 shape=(len(groups), L + 1))
    return dict(tokens=seq[:, :-1].astype(jnp.int32),
                labels=seq[:, 1:].astype(jnp.int32),
                mask=jnp.ones((len(groups), L), jnp.int32))


def init(key, model):
    import jax
    from repro.models import init_params

    return jax.jit(lambda k: init_params(k, _lm()))(key)


def split(params, model):
    from repro.models import split_trainable

    return split_trainable(params, _lm())


def loss_fn(model):
    from repro.models import lm_loss, merge_trainable

    cfg = _lm()
    return lambda tr, fz, batch, rng: lm_loss(merge_trainable(tr, fz, cfg),
                                              cfg, batch)


def reference_loss(model, params, frozen, batch, precision):
    import jax
    from repro.models import lm_loss, merge_trainable

    cfg = _lm()
    with jax.default_matmul_precision(precision.name.lower()):
        return lm_loss(merge_trainable(params, frozen, cfg), cfg, batch)


def train_flops_per_sample(model):
    cfg = _lm()
    return 6 * model["seq_len"] * cfg.n_layers * 12 * cfg.d_model ** 2


def tiny(model):
    pass


# -- a cell of it -------------------------------------------------------------

@pytest.fixture
def kind(monkeypatch):
    from repro.launch import compilecache

    monkeypatch.setattr(compilecache, "enable", lambda *a, **k: "")
    monkeypatch.setitem(sys.modules, f"bench.models.{KIND}",
                        sys.modules[__name__])


def _cell(mesh=None):
    cfg = dict(
        model=dict(kind=KIND, seq_len=16, n_groups=4),
        deployment=dict(m=12, strategy="fedawe", resident_dtype="float32",
                        c_max=0, echo_kernel=True, seeds=4 if mesh else 1,
                        mesh=mesh),
        training=dict(s=2, batch=4, eta_l=0.05, eta_g=1.0, lr_schedule=True,
                      grad_clip=0.5))
    traffic = dict(
        availability=dict(kind="markov", markov_up=0.5, markov_down=0.3,
                          base_p="from_data"),
        data=dict(samples_per_client=20, alpha=0.1, population_seed=0),
        sampling="uniform", chunk_rounds=2)
    # limits between the sound run's readings on the CPU (6e-5 and under)
    # and the bf16 control's and half batch's (global_gap 0.19 and over)
    limits = dict(count_mismatch=0, loss_gap=5e-4, global_gap=5e-4,
                  client_gap=5e-4)
    return harness.Cell(name=KIND, chips=4 if mesh else 1, cfg=cfg,
                        traffic=traffic, limits=limits, per_layer=[])


def test_frozen_kind_sound_run_is_correct(kind):
    res = testing.run_tiny(_cell())
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"rounds_per_s", "hbm_peak_gb", "setup_s"}


def test_frozen_kind_control_is_not_correct(kind):
    cell = _cell()
    task = harness.traffic_mod.make_task(cell.cfg, cell.traffic,
                                         testing.SEED)
    rows = harness.sample_rows(cell, testing.SEED)
    want = harness.follow(cell, testing.SEED, task, rows)
    got = harness.follow(cell, testing.SEED, task, rows, dtype="bfloat16")
    ok, shown = harness.verdict(harness.compare(cell, task, got, want),
                                cell.limits)
    assert not ok, shown


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_frozen_kind_fault_is_not_correct(fault, kind, monkeypatch):
    testing.FAULTS[fault](monkeypatch)
    res = testing.run_tiny(_cell())
    assert not res["correct"], res["check"]


def test_frozen_base_is_a_runtime_argument(kind):
    """The compiled chunk program takes the frozen base's bytes as an
    argument, beside the state, the sampler's carry, the store and the
    key; the client rows hold only the adapters; nothing of the base is
    donated."""
    import jax

    cell = _cell()
    task, prog, _, _, _ = harness.setup(cell, testing.SEED)
    nbytes = lambda tree: sum(l.nbytes for l in jax.tree.leaves(tree))
    frozen = nbytes(task.frozen)
    rest = nbytes((prog.state, prog.sampler_state, prog.store,
                   prog.data_key))
    assert frozen > 10 * nbytes(task.params) > 0
    assert prog.memory()["argument_size_in_bytes"] == frozen + rest
    assert prog.state.clients_tr.shape == (
        12, len(harness.reference.flatten(task.params)))
    prog.run(prog.K)
    assert not any(l.is_deleted() for l in jax.tree.leaves(task.frozen))
    prog.free()


def test_seed_mesh_refuses_a_frozen_base(kind):
    cell = _cell(mesh="seed")
    task = harness.traffic_mod.make_task(cell.cfg, cell.traffic,
                                         testing.SEED)
    with pytest.raises(SystemExit, match="no frozen base"):
        harness.Program(cell, task)
