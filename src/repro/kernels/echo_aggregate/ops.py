"""jit'd wrappers: flat buffers (or flattened pytrees) -> kernel.

``echo_aggregate_flat`` is the single-launch FedAWE server update over the
flat ``[m, N]`` substrate (core/flatten.py); ``echo_aggregate_tree`` is the
drop-in used by the tree-state FedAWE strategy when FLConfig.use_kernel is
set — it concatenates all leaves through a FlatSpec so a round issues exactly
ONE ``pallas_call`` regardless of leaf count, then unflattens the result.
The jnp reference path stays the default inside the 512-device dry-run
lowering (Pallas-on-CPU requires interpret mode)."""
from __future__ import annotations

import jax

from repro.core.flatten import FlatSpec
from repro.kernels.echo_aggregate.kernel import (echo_aggregate_fused_pallas,
                                                 echo_aggregate_pallas)
from repro.kernels.echo_aggregate.ref import (echo_aggregate_fused_ref,
                                              echo_aggregate_ref)


def _use_interpret():
    # TPU runs the Mosaic kernel; everywhere else interpret mode.
    return jax.default_backend() != "tpu"


def echo_aggregate(x, y, mask, echo, eta_g, *, use_pallas=True, block_n=4096):
    """x, y: [m, ...]; returns aggregated [...] (f32). No empty-round guard —
    callers apply the W = I rule themselves."""
    m = x.shape[0]
    flat_x = x.reshape(m, -1)
    flat_y = y.reshape(m, -1)
    if use_pallas:
        out = echo_aggregate_pallas(flat_x, flat_y, mask, echo, eta_g,
                                    block_n=block_n,
                                    interpret=_use_interpret())
    else:
        out = echo_aggregate_ref(flat_x, flat_y, mask, echo, eta_g)
    return out.reshape(x.shape[1:])


def echo_aggregate_flat(clients_flat, x_end_flat, global_flat, mask, echo,
                        eta_g, *, use_pallas=True, upload=None):
    """Fused FedAWE update on the flat substrate: one launch, guard included.

    clients_flat, x_end_flat: [m, N] start / post-local-SGD stacks;
    global_flat: [N] previous global (returned verbatim on empty rounds).
    ``upload`` ([m], optional) is the mid-round dropout survival mask
    (core/faults.py) fused into the kernel weights. Returns the new [N]
    f32 global."""
    if use_pallas:
        return echo_aggregate_fused_pallas(
            clients_flat, x_end_flat, global_flat, mask, echo, eta_g,
            interpret=_use_interpret(), upload=upload)
    return echo_aggregate_fused_ref(clients_flat, x_end_flat, global_flat,
                                    mask, echo, eta_g, upload=upload)


def echo_aggregate_tree(clients_tr, x_end, mask, echo, eta_g, global_tr, *,
                        use_pallas=True, upload=None):
    """Tree version over client-stacked trainables — single fused launch.

    clients_tr: x_i start models [m, ...]; x_end: post-local-SGD models
    [m, ...] (passed directly — no x − G reconstruction); global_tr: the
    previous global for the fused empty-round guard. All leaves are raveled
    into one contiguous [m, N] buffer so the whole round is exactly one
    ``pallas_call``; the result is unflattened back to leaf dtypes."""
    spec = FlatSpec.from_tree(global_tr)
    out = echo_aggregate_flat(
        spec.flatten_stacked(clients_tr), spec.flatten_stacked(x_end),
        spec.flatten(global_tr), mask, echo, eta_g,
        use_pallas=use_pallas, upload=upload)
    return spec.unflatten(out)
