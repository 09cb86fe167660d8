"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

TPU adaptation notes: the CUDA flash-attention algorithm is re-blocked for
the TPU memory hierarchy — the grid is ``(batch*head, q blocks, kv
blocks)`` with the kv axis innermost, so each step holds one
``[block_q, head_dim]`` query tile and one ``[block_k, head_dim]`` K/V
tile in VMEM, and the online-softmax running max/sum and the output
accumulator live in f32 VMEM scratch across the kv axis.  VMEM use is
independent of the key length, which the set mixer's 65,536-agent pooling
needs (whole K/V rows of that length do not fit).  Causal steps past the
diagonal skip their compute and re-use the last live K/V block, so their
DMA is skipped too.  GQA is handled by the K/V ``index_map`` (query head h
reads KV head ``h // group``), so repeated KV heads are never materialised.

Validated in ``interpret=True`` mode on CPU against ``ref.py``; compiled
for TPU where ``jax.devices()[0].platform == 'tpu'``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: full f32 matmuls on the MXU (the default may take bf16 passes), so the
#: kernel matches the f32 reference the FL steps are tested against
_F32 = jax.lax.Precision.HIGHEST


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale,
            block_q, block_k, causal, window, nk):
    iq, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_first = iq * block_q
    k_first = j * block_k
    live = True
    if causal:           # some key of this block is at or before a query
        live = k_first <= q_first + block_q - 1
    if window:           # some key of this block is inside a query's window
        live = jnp.logical_and(live,
                               k_first + block_k - 1 > q_first - window)

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, D]
        k = k_ref[0].astype(jnp.float32)                    # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        if causal or window:
            q_pos = q_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= k_pos <= q_pos
            if window:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, precision=_F32, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0,
                         block_q=128, block_k=128, interpret=False):
    """q: [BHq, Sq, D]; k/v: [BHkv, Sk, D] with BHq = BHkv * group."""
    BH, Sq, D = q.shape
    BHkv, Sk, _ = k.shape
    group = BH // BHkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nk = Sk // block_k
    scale = 1.0 / math.sqrt(D)

    def kv_index(bh, iq, j):
        if causal:       # past the diagonal: re-use the last live block
            j = jnp.minimum(j, ((iq + 1) * block_q - 1) // block_k)
        return (bh // group, j, 0)

    kernel = functools.partial(_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal,
                               window=window, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, Sq // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, iq, j: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, iq, j: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
