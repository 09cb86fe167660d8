"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.layer_agg import layer_agg_op, layer_agg_ref
from repro.kernels.rmsnorm import rmsnorm_op, rmsnorm_ref


def _fa_ref(q, k, v, causal, window):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    qb = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kb = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vb = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    o = attention_ref(qb, kb, vb, causal=causal, window=window)
    return o.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,causal,window,bq,bk", [
    (2, 128, 4, 2, 64, True, 0, 64, 64),
    (1, 256, 8, 1, 32, True, 0, 128, 64),
    (2, 128, 2, 2, 128, True, 32, 32, 32),
    (1, 64, 4, 4, 64, False, 0, 64, 64),
    (1, 128, 6, 2, 64, True, 0, 128, 128),
])
def test_flash_attention_sweep(B, S, Hq, Hkv, D, causal, window, bq, bk,
                               dtype, tol):
    key = jax.random.PRNGKey(B * S + Hq)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = _fa_ref(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,Sq,Sk,D,bq,bk", [
    # the set-mixer regime: a few seed queries pooling over a large
    # (block-aligned) agent axis, non-causal, rectangular Sq != Sk
    (2, 4, 512, 32, 4, 128),
    (1, 4, 4096, 32, 4, 256),
    (3, 8, 256, 64, 8, 64),
])
def test_flash_attention_rectangular_noncausal(B, Sq, Sk, D, bq, bk):
    """ops/ref parity for the attention-reduce shape class (Pallas
    interpret mode) — seed queries over agent keys, no masking."""
    key = jax.random.PRNGKey(Sq * Sk + D)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, 1, D))
    k = jax.random.normal(ks[1], (B, Sk, 1, D))
    v = jax.random.normal(ks[2], (B, Sk, 1, D))
    out = flash_attention(q, k, v, causal=False, block_q=bq, block_k=bk,
                          interpret=True)
    ref = _fa_ref(q, k, v, False, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_attention_reduce_matches_ref():
    """The set mixer's pooling entry point is the oracle off-TPU (and the
    kernel's math on it): [B, S, D] queries over [B, N, D] keys/values."""
    from repro.core.marl.networks import attention_reduce
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 4, 32))
    k = jax.random.normal(ks[1], (2, 100, 32))
    v = jax.random.normal(ks[2], (2, 100, 32))
    out = attention_reduce(q, k, v)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


@hypothesis.given(
    n=st.integers(1, 8), l=st.integers(1, 6),
    dpow=st.integers(4, 9), seed=st.integers(0, 99),
    zero_col=st.booleans())
@hypothesis.settings(max_examples=15, deadline=None)
def test_layer_agg_property(n, l, dpow, seed, zero_col):
    D = 2 ** dpow
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    U = jax.random.normal(ks[0], (n, l, D))
    M = (jax.random.uniform(ks[1], (n, l)) > 0.3).astype(jnp.float32)
    if zero_col:
        M = M.at[:, 0].set(0.0)        # a layer NO client trained
    w = jax.random.uniform(ks[2], (n,)) * 10 + 0.1
    out = layer_agg_op(U, M, w, block_d=64, interpret=True)
    ref = layer_agg_ref(U, M, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)
    if zero_col:
        np.testing.assert_allclose(np.asarray(out[0]), 0.0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("shape", [(8, 64), (2, 16, 128), (3, 4, 5, 256),
                                   # odd row counts: rows pad to the 8-row tile
                                   (7, 64), (3, 11, 128), (1, 256)])
def test_rmsnorm_sweep(shape, dtype, tol):
    key = jax.random.PRNGKey(sum(shape))
    x = (jax.random.normal(key, shape) * 3).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(key, 1), shape[-1:]).astype(dtype)
    out = rmsnorm_op(x, s, interpret=True)
    ref = rmsnorm_ref(x.reshape(-1, shape[-1]), s).reshape(shape)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_aggregate_stacked_leaf_matches_layerwise():
    """Kernel path == repro.core.aggregation.layerwise_aggregate on stacked
    leaves (the production Step-2 path)."""
    from repro.core.aggregation import layerwise_aggregate
    from repro.kernels.layer_agg import aggregate_stacked_leaf
    key = jax.random.PRNGKey(0)
    L, shape = 4, (4, 8, 16)
    gp = jax.random.normal(key, shape)
    ups = [jax.random.normal(jax.random.fold_in(key, i), shape) for i in range(3)]
    masks = [jnp.asarray([1., 1., 0., 0.]), jnp.asarray([1., 1., 1., 0.]),
             jnp.asarray([1., 0., 0., 0.])]
    w = [2.0, 1.0, 3.0]
    out_k = aggregate_stacked_leaf(gp, ups, masks, w, interpret=True)
    masks_b = [{"x": m.reshape(L, 1, 1)} for m in masks]
    out_r = layerwise_aggregate({"x": gp}, [{"x": u} for u in ups], masks_b, w)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r["x"]),
                               atol=1e-5, rtol=1e-4)
