"""Plain FedAWE reference, and the comparison that decides ``correct``.

``follow`` re-derives a cell's first rounds from the same seed-made inputs
(weights, the frozen base, per-sample arrays, shards, base probabilities,
PRNG keys) without any of the program's code: availability draws of every
kind (``bench/availability/<kind>.py``), cohort selection with deferral,
the uniform per-client sampler, local SGD through the model module's
reference loss (``bench/models/<kind>.py``, plain ``jax.numpy``, f32 at
``Precision.HIGHEST``), FedAWE's adaptive echo and gossip mean, the tau
and client-state updates, and the resident dtype of the client stack.
The random streams are the program's documented ones: per round the state
key splits into (next, availability, local) keys, availability draws one
uniform a client from its key, and the sampler draws
``randint(fold_in(data_key, t), (m, s*b), 0, counts)`` columns into each
client's shard.

``dtype=bfloat16`` runs the same rounds one precision below what the
configuration states (the control); ``half_batch`` trains on the first half
of every batch (a planted fault).  ``compare`` turns the program's and the
reference's observations into the numbers held against the cell's limits.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import List

import numpy as np

from bench import availability, models


@dataclasses.dataclass
class Observed:
    """What one seed's first K rounds produce, as the comparison sees it."""
    loss: np.ndarray          # [K] mean local loss over trained clients
    n_active: np.ndarray      # [K] clients that trained
    n_deferred: np.ndarray    # [K] available clients over the cohort cap
    tau: np.ndarray           # [m] last round each client delivered, or -1
    global_flat: np.ndarray   # [N] global model after K rounds
    rows: np.ndarray          # [R, N] sampled client rows after K rounds
    row_ids: np.ndarray       # [R] which clients ``rows`` holds


def leaf_offsets(params) -> np.ndarray:
    """Where each leaf starts in the flat vector, and its end: leaves in
    the order ``jax.tree.leaves`` visits a dict tree (sorted keys), the
    order of the program's flat substrate."""
    import jax

    sizes = [int(np.prod(l.shape)) for l in jax.tree.leaves(params)]
    return np.concatenate([[0], np.cumsum(sizes)]).astype(int)


def flatten(params) -> np.ndarray:
    """The weights as one f32 vector, leaves in ``leaf_offsets`` order."""
    import jax

    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree.leaves(params)])


def split_leaves(flat, offsets):
    return [flat[..., offsets[i]:offsets[i + 1]]
            for i in range(len(offsets) - 1)]


@functools.lru_cache(maxsize=None)
def _make_block_fn(*, model_json, s, b, grad_clip, eta_g, dtype, half_batch,
                   resident, precision):
    """Local SGD for one block of clients and their share of the FedAWE
    sum, in one jitted call, through the model module's reference loss.

    starts come from ``history[start_slot]`` (the global each client last
    received, rounded to the resident dtype), so the call's shapes never
    change with the round.  The frozen base enters as it is, in ``dtype``
    where it is floating point."""
    import jax
    import jax.numpy as jnp

    model = json.loads(model_json)
    loss_of = models.load(model["kind"]).reference_loss
    precision = jax.lax.Precision[precision.upper()]
    bb = b // 2 if half_batch else b

    def low(a):
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) \
            else a

    def sgd(params, frozen, batch, eta):
        def step(p, mb):
            loss, g = jax.value_and_grad(
                lambda q: loss_of(model, q, frozen, mb, precision))(p)
            sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                     for l in jax.tree.leaves(g))
            scale = jnp.minimum(1.0, grad_clip
                                / jnp.maximum(jnp.sqrt(sq), 1e-12))
            p = jax.tree.map(
                lambda pp, gg: (pp - (eta * scale).astype(dtype) * gg)
                .astype(dtype), p, g)
            return p, loss.astype(jnp.float32)

        p, losses = jax.lax.scan(step, params, batch)
        return p, jnp.mean(losses)

    @jax.jit
    def block(history, frozen, start_slot, weight, echo, arrays, ids, eta):
        # history: tree of [K+1, ...] f32 globals; start_slot, weight,
        # echo: [B]; ids: [B, s*b] sample indices
        starts = jax.tree.map(
            lambda h: h[start_slot].astype(resident).astype(jnp.float32),
            history)
        batch = {k: low(v[ids].reshape((ids.shape[0], s, b) + v.shape[1:])
                        [:, :, :bb]) for k, v in arrays.items()}
        p0 = jax.tree.map(lambda a: a.astype(dtype), starts)
        ends, losses = jax.vmap(sgd, in_axes=(0, None, 0, None))(
            p0, jax.tree.map(low, frozen), batch, eta.astype(dtype))
        # FedAWE echo: x_i - eta_g (t - tau_i) (x_i - y_i), summed with
        # weight 1 per trained client (0 for the block's padding)
        part = jax.tree.map(
            lambda x, y: jnp.sum(
                (weight[:, None] * (x.reshape(x.shape[0], -1)
                                    - eta_g * echo[:, None]
                                    * (x - y.astype(jnp.float32))
                                    .reshape(x.shape[0], -1))).astype(dtype),
                axis=0).astype(jnp.float32).reshape(x.shape[1:]),
            starts, ends)
        return part, losses

    return block


def follow(cfg: dict, traffic: dict, *, arrays, client_indices, base_p,
           params, frozen, state_key, data_key, rounds: int, row_ids,
           dtype="float32", half_batch=False, precision=None) -> Observed:
    """Run ``rounds`` FedAWE rounds of one seed replicate from its initial
    inputs and observe them as ``Observed``.  ``arrays`` are the
    per-sample arrays, ``params`` the trainable weights and ``frozen`` the
    base the model module's reference loss is given as it is.  Matrix
    products run at ``Precision.HIGHEST`` in f32 and at the default
    precision in a lower ``dtype``; ``precision`` overrides that choice."""
    import jax
    import jax.numpy as jnp

    dep, tr = cfg["deployment"], cfg["training"]
    m, s, b = dep["m"], tr["s"], tr["batch"]
    c_max = dep["c_max"]
    if dep["strategy"] != "fedawe":
        raise ValueError("the reference implements FedAWE only")
    if traffic["sampling"] != "uniform":
        raise ValueError("the reference implements the uniform sampler only")
    client_block = models.of(cfg).CLIENT_BLOCK
    dt = jnp.dtype(dtype)
    resident = jnp.dtype(dep["resident_dtype"])
    q = s * b
    counts = np.array([len(ix) for ix in client_indices], np.int32)
    cap = int(counts.max())
    shard = np.zeros((m, cap), np.int64)
    for i, ix in enumerate(client_indices):
        shard[i, :len(ix)] = ix
    counts_dev = jnp.asarray(counts)
    if precision is None:
        precision = "highest" if dt == jnp.float32 else "default"
    block = _make_block_fn(
        model_json=json.dumps(cfg["model"], sort_keys=True), s=s, b=b,
        grad_clip=tr["grad_clip"], eta_g=tr["eta_g"], dtype=dt,
        half_batch=half_batch, resident=resident, precision=precision)
    avail = availability.make(traffic["availability"], base_p)
    cols_of = jax.jit(lambda k: jax.random.randint(
        k, (m, q), 0, counts_dev[:, None]))

    g0 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    history = jax.tree.map(
        lambda a: jnp.zeros((rounds + 1,) + a.shape, jnp.float32)
        .at[0].set(a), g0)
    last = np.full((m,), -1, np.int64)     # round of last delivery
    tau = np.full((m,), -1, np.int64)
    arrays = {k: jnp.asarray(v) for k, v in arrays.items()}
    losses, n_act, n_def = [], [], []
    key = jnp.asarray(state_key)
    for t in range(rounds):
        keys = jax.random.split(key, 3)
        key, k_av = keys[0], keys[1]
        mask = avail.draw(k_av, t)
        active = np.nonzero(mask)[0]
        # the lowest-index c_max available clients train; the rest defer
        trained = active[:c_max] if c_max else active
        n_act.append(len(trained))
        n_def.append(len(active) - len(trained))
        cols = np.asarray(cols_of(jax.random.fold_in(data_key, t)))
        eta = jnp.float32(tr["eta_l"]) / jnp.sqrt(
            jnp.float32(t) / 10.0 + 1.0) if tr["lr_schedule"] \
            else jnp.float32(tr["eta_l"])
        total = jax.tree.map(jnp.zeros_like, g0)
        client_losses = []
        for lo in range(0, len(trained), client_block):
            ids_c = trained[lo:lo + client_block]
            n = len(ids_c)
            pad = client_block - n
            sel = np.concatenate([ids_c, np.zeros(pad, np.int64)])
            ids = shard[sel[:, None], cols[sel]]
            weight = np.concatenate([np.ones(n), np.zeros(pad)])
            echo = (t - tau[sel]).astype(np.float32)
            part, loss = block(history, frozen, jnp.asarray(last[sel] + 1),
                               jnp.asarray(weight, jnp.float32),
                               jnp.asarray(echo), arrays,
                               jnp.asarray(ids, jnp.int32), eta)
            total = jax.tree.map(jnp.add, total, part)
            client_losses.append(np.asarray(loss)[:n])
        if len(trained):
            new = jax.tree.map(lambda x: x / len(trained), total)
            losses.append(float(np.mean(np.concatenate(client_losses))))
        else:
            new = jax.tree.map(lambda h: h[t], history)
            losses.append(0.0)
        history = jax.tree.map(lambda h, x: h.at[t + 1].set(x), history, new)
        tau[trained] = t
        last[trained] = t
    hist_flat = np.stack([flatten(jax.tree.map(lambda h: h[k], history))
                          for k in range(rounds + 1)])
    row_ids = np.asarray(row_ids)
    rows = hist_flat[last[row_ids] + 1]
    rows = np.asarray(jnp.asarray(rows).astype(resident).astype(jnp.float32))
    return Observed(loss=np.array(losses), n_active=np.array(n_act),
                    n_deferred=np.array(n_def), tau=tau,
                    global_flat=hist_flat[rounds], rows=rows,
                    row_ids=row_ids)


def _norm_gap(prog_delta, ref_delta, offsets):
    """Worst leaf of |‖Δprog‖ - ‖Δref‖| / max(‖Δref‖, median leaf ‖Δref‖).

    Leaves the reference leaves unmoved to rounding (‖Δref‖ under a
    thousandth of the median leaf's) are left out, by that rule on the
    reference and not by name."""
    p = [float(np.linalg.norm(x.astype(np.float64)))
         for x in split_leaves(prog_delta, offsets)]
    r = [float(np.linalg.norm(x.astype(np.float64)))
         for x in split_leaves(ref_delta, offsets)]
    med = float(np.median(r))
    if med == 0.0:
        return 0.0 if max(p) == 0.0 else math.inf
    return max(abs(pi - ri) / max(ri, med)
               for pi, ri in zip(p, r) if ri >= 1e-3 * med)


def compare(prog: List[Observed], ref: List[Observed], init_flat,
            offsets, resident_dtype: str) -> dict:
    """The numbers held against a cell's limits, worst over its seeds:

    * ``count_mismatch``: rounds whose available or deferred counts
      differ, clients whose tau differs, and sampled client rows that the
      reference leaves at their initial value but the program changed;
    * ``loss_gap``: worst round's |loss - reference| / |reference|;
    * ``global_gap``: the global model's change over the rounds, by the
      worst leaf (``_norm_gap``);
    * ``client_gap``: the same for every sampled client row the reference
      updated."""
    import jax.numpy as jnp

    init_res = np.asarray(jnp.asarray(init_flat).astype(resident_dtype)
                          .astype(jnp.float32))
    out = dict(count_mismatch=0, loss_gap=0.0, global_gap=0.0,
               client_gap=0.0)
    for p, r in zip(prog, ref):
        mism = int(np.sum(p.n_active != r.n_active)
                   + np.sum(p.n_deferred != r.n_deferred)
                   + np.sum(p.tau != r.tau))
        for k in range(len(r.loss)):
            if r.loss[k] == 0.0:
                gap = 0.0 if p.loss[k] == 0.0 else math.inf
            else:
                gap = abs(p.loss[k] - r.loss[k]) / abs(r.loss[k])
            if not math.isfinite(p.loss[k]):
                gap = math.inf
            out["loss_gap"] = max(out["loss_gap"], gap)
        out["global_gap"] = max(out["global_gap"], _norm_gap(
            p.global_flat - init_flat, r.global_flat - init_flat, offsets))
        for j in range(len(r.row_ids)):
            if r.tau[r.row_ids[j]] < 0:
                mism += int(not np.array_equal(p.rows[j], init_res))
            else:
                out["client_gap"] = max(out["client_gap"], _norm_gap(
                    p.rows[j] - init_res, r.rows[j] - init_res, offsets))
        out["count_mismatch"] += mism
    return out
