"""Pallas TPU kernel: fused FedAWE echo + implicit-gossip aggregation.

The per-round server update touches every byte of the client-stacked
parameters (read x_i, read y_i, write mean) and is purely memory-bound — the
paper's own hot loop. Fusing echo + masked mean into one pass halves HBM
traffic vs. the two-op jnp formulation (materializing x† then reducing).

Tiling (fused kernel): a 2-D grid, column blocks of the flattened
parameter dimension N by client blocks of m.  The client axis is the
reduction: it runs innermost, marked ``arbitrary``, and sums into the f32
output block, which stays resident across it.  Each step streams a
[BLOCK_M, BLOCK_N] tile of x and y through VMEM, so VMEM use does not grow
with m.  Every operand is 2-D: the per-client weight and echo ride along
as [m, 1] columns blocked with the tiles, g and the output as [1, N] rows.
A block's last two dimensions must match the (8, 128) tile or span the
array, which a 1-D block stops doing once ``vmap`` (the seed executors)
adds a leading axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Fused-kernel tile: one [256, 2048] f32 tile of x and one of y, double
# buffered, is 8 MiB of VMEM plus the kernel's temporaries, inside v5e's
# default 16 MiB scoped limit; tiles twice that size fail to compile there.
# BLOCK_N stays a multiple of the 128-lane tile; BLOCK_M a multiple of the
# 16-row sublane tile of bf16 stacks.
BLOCK_M = 256
BLOCK_N = 2048


def _kernel(mask_ref, echo_ref, denom_ref, x_ref, y_ref, o_ref, *, eta_g):
    x = x_ref[...].astype(jnp.float32)          # [m, BN]
    y = y_ref[...].astype(jnp.float32)
    w = mask_ref[...].astype(jnp.float32)       # [m]
    e = echo_ref[...].astype(jnp.float32)       # [m]
    xd = x - eta_g * e[:, None] * (x - y)       # adaptive innovation echoing
    acc = jnp.sum(w[:, None] * xd, axis=0)      # implicit-gossip masked sum
    o_ref[...] = (acc / denom_ref[0]).astype(o_ref.dtype)


def _fused_kernel(stat_ref, w_ref, echo_ref, x_ref, y_ref, g_ref, o_ref, *,
                  eta_g, m):
    """Full FedAWE server update in one sweep: echo + mask + gossip mean +
    empty-round guard (W = I: fall back to the previous global g).

    Grid step (j, i) adds client block i's weighted echoed models to
    column block j of the output; the last client block divides by the
    denominator ``stat[0, 0]`` or, when no client delivered
    (``stat[0, 1]``, the weight sum, is 0), writes g instead."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # [BM, BN] client starts
    y = y_ref[...].astype(jnp.float32)          # [BM, BN] post-local-SGD
    w = w_ref[...]                              # [BM, 1] f32 weights
    e = echo_ref[...]                           # [BM, 1] f32 echo
    wxd = w * (x - eta_g * e * (x - y))
    if m % x.shape[0]:
        # rows past m in the last client block hold unspecified values
        row = i * x.shape[0] + jax.lax.broadcasted_iota(jnp.int32,
                                                        wxd.shape, 0)
        wxd = jnp.where(row < m, wxd, 0.0)
    o_ref[...] += jnp.sum(wxd, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        mean = o_ref[...] / stat_ref[0, 0]      # max(sum of weights, 1)
        o_ref[...] = jnp.where(stat_ref[0, 1] > 0.0, mean, g_ref[...])


def echo_aggregate_pallas(x, y, mask, echo, eta_g, *, block_n=4096,
                          interpret=True):
    """x, y: [m, N]; mask, echo: [m]. Returns [N] f32 gossip mean.

    interpret=True executes the kernel body on CPU (this container);
    on TPU pass interpret=False for the compiled Mosaic kernel.
    """
    m, N = x.shape
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)[None]

    pad = (-N) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
    Np = N + pad
    grid = (Np // block_n,)

    out = pl.pallas_call(
        functools.partial(_kernel, eta_g=float(eta_g)),  # flcheck: ignore[R1] -- eta_g is static FLConfig config baked in at trace time, not a traced value
        grid=grid,
        in_specs=[
            pl.BlockSpec((m,), lambda j: (0,)),          # mask
            pl.BlockSpec((m,), lambda j: (0,)),          # echo
            pl.BlockSpec((1,), lambda j: (0,)),          # denom
            pl.BlockSpec((m, block_n), lambda j: (0, j)),  # x
            pl.BlockSpec((m, block_n), lambda j: (0, j)),  # y
        ],
        out_specs=pl.BlockSpec((block_n,), lambda j: (j,)),
        out_shape=jax.ShapeDtypeStruct((Np,), jnp.float32),
        interpret=interpret,
    )(mask.astype(jnp.float32), echo.astype(jnp.float32), denom, x, y)
    return out[:N]


def echo_aggregate_fused_pallas(x, y, g, mask, echo, eta_g, *,
                                interpret=True, upload=None):
    """Single-launch FedAWE aggregation over the flat substrate.

    x, y: [m, N] client start / end stacks; g: [N] previous global (the
    empty-round fallback); mask, echo: [m]. Returns [N] f32 — the whole
    server update (echo, mask, gossip mean, empty-round guard) is one
    ``pallas_call`` regardless of how many pytree leaves N concatenates
    or how many clients m holds.

    ``upload`` ([m], optional) threads the mid-round dropout mask of
    core/faults.py into the weights: they become mask*upload, and the
    guard counts delivering clients.
    """
    m, N = x.shape
    w = mask.astype(jnp.float32)
    if upload is not None:
        w = w * upload.astype(jnp.float32)
    total = jnp.sum(w)
    stat = jnp.stack([jnp.maximum(total, 1.0), total])[None]

    pad = (-N) % BLOCK_N
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
        g = jnp.pad(g, (0, pad))
    Np = N + pad
    bm = min(m, BLOCK_M)

    col = pl.BlockSpec((bm, 1), lambda j, i: (i, 0))
    stack = pl.BlockSpec((bm, BLOCK_N), lambda j, i: (i, j))
    row = pl.BlockSpec((1, BLOCK_N), lambda j, i: (0, j))
    return pl.pallas_call(
        functools.partial(_fused_kernel, eta_g=float(eta_g), m=m),  # flcheck: ignore[R1] -- eta_g is static FLConfig config baked in at trace time, not a traced value
        grid=(Np // BLOCK_N, pl.cdiv(m, bm)),
        in_specs=[pl.BlockSpec((1, 2), lambda j, i: (0, 0)), col, col,
                  stack, stack, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(stat, w[:, None], echo.astype(jnp.float32)[:, None], x, y,
      g.astype(jnp.float32)[None])[0, :N]
