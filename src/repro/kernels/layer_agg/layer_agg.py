"""Pallas kernel for DR-FL layer-aligned aggregation (paper Step 2).

This is the server-side hot spot when many clients upload large layer-wise
updates: for stacked updates ``U [N, R, D]`` (N clients, R rows, D
flattened per-row params), masks ``M [N, R]`` and data-size weights
``w [N]``:

    out[r, d] = sum_n w_n * M[n,r] * U[n,r,d] / max(sum_n w_n * M[n,r], eps)

One fused pass: the unfused XLA version materialises the ``[N, R, D]``
weighted product and a broadcasted denominator; here each grid step reduces
a ``[bn, br, bd]`` VMEM tile straight into an f32 accumulator — HBM traffic
drops from ~3·N·R·D to ~N·R·D reads + R·D writes.

TPU tiling: the last two dims of every block are (multiple of 8, multiple
of 128) or the whole array.  So the update block is ``(bn, br, bd)``, the
weighted mask travels transposed as ``wm [R, N]`` blocked ``(br, bn)``
(``bn`` is the whole N or a multiple of 128), and the output block is
``(br, bd)``.  Grid: ``(R // br, D // bd, N // bn)``; the client axis is
the innermost reduction, accumulated in VMEM scratch and normalised at its
last step.  Tile sizes are chosen by ``ops.plan_tiles`` from a VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, wm_ref, o_ref, acc_ref, den_ref, *, bn, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    wm = wm_ref[...].astype(jnp.float32)                  # [br, bn]
    acc = acc_ref[...]                                    # [br, bd]
    for n in range(bn):         # per-row weights: a lane column per client
        acc = acc + wm[:, n:n + 1] * u_ref[n].astype(jnp.float32)
    acc_ref[...] = acc
    den_ref[...] += jnp.sum(wm, axis=1, keepdims=True)    # [br, 1]

    @pl.when(k == nk - 1)
    def _():
        den = den_ref[...]
        o_ref[...] = jnp.where(den > 0,
                               acc_ref[...] / jnp.maximum(den, 1e-12),
                               0.0).astype(o_ref.dtype)


def layer_agg(updates, wm_t, *, br, bd, bn, interpret=False):
    """updates: [N, R, D]; wm_t: [R, N] weighted masks (w_n * M[n, r],
    transposed) -> [R, D] float32.  N % bn, R % br and D % bd must be 0."""
    N, R, D = updates.shape
    assert wm_t.shape == (R, N), (wm_t.shape, updates.shape)
    assert R % br == 0 and D % bd == 0 and N % bn == 0, (N, R, D, bn, br, bd)
    nk = N // bn
    return pl.pallas_call(
        functools.partial(_kernel, bn=bn, nk=nk),
        grid=(R // br, D // bd, nk),
        in_specs=[
            pl.BlockSpec((bn, br, bd), lambda i, j, k: (k, i, j)),
            pl.BlockSpec((br, bn), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((br, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, bd), jnp.float32),
                        pltpu.VMEM((br, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="layer_agg",
    )(updates, wm_t)
