"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(deliverable c: per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.echo_aggregate.kernel import (BLOCK_M,
                                                 echo_aggregate_fused_pallas,
                                                 echo_aggregate_pallas)
from repro.kernels.echo_aggregate.ops import echo_aggregate_tree
from repro.kernels.echo_aggregate.ref import (echo_aggregate_fused_ref,
                                              echo_aggregate_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ops import flash_mha
from repro.kernels.flash_attention.ref import mha_ref


# ---------------------------------------------------------------------------
# echo_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,N,dtype,block", [
    (2, 17, jnp.float32, 8), (4, 100, jnp.float32, 64),
    (16, 4096, jnp.float32, 1024), (8, 1000, jnp.bfloat16, 256),
    (32, 5000, jnp.bfloat16, 2048), (3, 1, jnp.float32, 8),
])
def test_echo_aggregate_sweep(m, N, dtype, block):
    rng = np.random.default_rng(m * N)
    x = jnp.asarray(rng.normal(size=(m, N)), dtype)
    y = jnp.asarray(rng.normal(size=(m, N)), dtype)
    mask = jnp.asarray((rng.random(m) < 0.7).astype(np.float32))
    echo = jnp.asarray(rng.integers(1, 12, m).astype(np.float32))
    out = echo_aggregate_pallas(x, y, mask, echo, 1.7, block_n=block)
    ref = echo_aggregate_ref(x, y, mask, echo, 1.7)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=tol,
                               atol=tol)


@given(st.integers(0, 10 ** 6))
@settings(max_examples=15)
def test_echo_aggregate_property(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(2, 12))
    N = int(rng.integers(1, 300))
    x = jnp.asarray(rng.normal(size=(m, N)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(m, N)).astype(np.float32))
    mask = jnp.asarray((rng.random(m) < 0.5).astype(np.float32))
    echo = jnp.asarray(rng.integers(1, 20, m).astype(np.float32))
    eta = float(rng.uniform(0.1, 2.0))
    out = echo_aggregate_pallas(x, y, mask, echo, eta, block_n=64)
    ref = echo_aggregate_ref(x, y, mask, echo, eta)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def _fused_inputs(m, N, dtype, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, N)), dtype)
    y = jnp.asarray(rng.normal(size=(m, N)), dtype)
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    mask = jnp.asarray((rng.random(m) < 0.6).astype(np.float32))
    echo = jnp.asarray(rng.integers(1, 9, m).astype(np.float32))
    upload = jnp.asarray((rng.random(m) < 0.7).astype(np.float32))
    return x, y, g, mask, echo, upload


@pytest.mark.parametrize("m,N,dtype,upload", [
    (8, 17, jnp.float32, False),
    (BLOCK_M, 700, jnp.float32, True),
    # several client blocks, the last one partial
    (BLOCK_M + 44, 999, jnp.float32, True),
    (2 * BLOCK_M + 88, 3000, jnp.bfloat16, False),
])
def test_echo_aggregate_fused_client_blocks(m, N, dtype, upload):
    """The fused kernel reduces over client blocks of BLOCK_M rows; rows
    past m in the last block must not leak into the mean."""
    x, y, g, mask, echo, up = _fused_inputs(m, N, dtype, m)
    up = up if upload else None
    out = echo_aggregate_fused_pallas(x, y, g, mask, echo, 1.3, upload=up)
    ref = echo_aggregate_fused_ref(x, y, g, mask, echo, 1.3, upload=up)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    empty = echo_aggregate_fused_pallas(x, y, g, jnp.zeros_like(mask), echo,
                                        1.3, upload=up)
    np.testing.assert_array_equal(np.asarray(empty), np.asarray(g))


def test_echo_aggregate_fused_vmap_matches_loop():
    """The seed executors vmap the kernel: each seed's slice equals the
    unbatched call, bit for bit."""
    seeds = [_fused_inputs(12, 300, jnp.float32, j) for j in range(3)]
    batched = jax.vmap(
        lambda x, y, g, mk, e, u: echo_aggregate_fused_pallas(
            x, y, g, mk, e, 0.9, upload=u))(
        *[jnp.stack(a) for a in zip(*seeds)])
    for j, args in enumerate(seeds):
        x, y, g, mk, e, u = args
        np.testing.assert_array_equal(
            np.asarray(batched[j]),
            np.asarray(echo_aggregate_fused_pallas(x, y, g, mk, e, 0.9,
                                                   upload=u)))


def test_echo_aggregate_tree_matches_strategy_path():
    """Kernel-path FedAWE aggregate == jnp-path FedAWE aggregate."""
    from repro.core.strategies import _fedawe_aggregate

    rng = np.random.default_rng(0)
    m = 8
    tree = {"a": jnp.asarray(rng.normal(size=(m, 6, 5)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(m, 11)).astype(np.float32))}
    G = jax.tree.map(lambda x: x * 0.05, tree)
    mask = jnp.asarray(np.array([1, 0, 1, 1, 0, 1, 0, 1], np.float32))
    tau = jnp.asarray(np.array([0, 1, -1, 2, 0, 1, 2, 3], np.int32))
    t = jnp.asarray(4, jnp.int32)
    global_tr = jax.tree.map(lambda x: x[0], tree)
    g_jnp, _, _, _ = _fedawe_aggregate(
        global_tr=global_tr, clients_tr=tree, G=G,
        mask=mask, t=t, tau=tau, probs=None, extra=(), eta_g=1.2,
        use_kernel=False)
    echo = (t - tau).astype(jnp.float32)
    x_end = jax.tree.map(lambda x, g: x - g, tree, G)
    g_kern = echo_aggregate_tree(tree, x_end, mask, echo, 1.2, global_tr)
    for k in tree:
        np.testing.assert_allclose(np.asarray(g_jnp[k]),
                                   np.asarray(g_kern[k]), rtol=1e-4,
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,L,S,D,window,softcap,causal", [
    (2, 4, 4, 64, 64, 32, None, 0.0, True),
    (1, 4, 2, 32, 64, 16, None, 0.0, True),       # GQA + suffix alignment
    (2, 2, 2, 64, 64, 32, 24, 0.0, True),          # sliding window
    (1, 2, 1, 64, 64, 64, None, 20.0, True),       # softcap
    (1, 2, 2, 64, 64, 32, None, 0.0, False),       # bidirectional
    (1, 8, 4, 128, 128, 64, 48, 30.0, True),       # everything at once
])
def test_flash_attention_sweep(B, H, K, L, S, D, window, softcap, causal):
    rng = np.random.default_rng(L + S)
    q = jnp.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, K, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, K, S, D)).astype(np.float32))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_l=16, block_s=16)
    G = H // K
    ref = mha_ref(q, jnp.repeat(k, G, 1), jnp.repeat(v, G, 1), causal=causal,
                  window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 4, 64, 32)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 32)), dtype)
    out = flash_attention(q, k, v, block_l=32, block_s=32)
    ref = mha_ref(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol,
                               atol=tol)


def test_flash_mha_wrapper_model_layout():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 32, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 32, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 32, 2, 16)).astype(np.float32))
    out = flash_mha(q, k, v, block_l=16, block_s=16)
    ref = flash_mha(q, k, v, use_pallas=False)
    assert out.shape == (2, 32, 4, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)
