"""Fused RMSNorm Pallas kernel (one HBM round-trip instead of XLA's
mean+rsqrt+mul chain). Rows tile over the grid; the feature dim stays whole
in VMEM (d <= 8192 across all assigned archs => <= 32 KiB f32 per row)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # [br, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x2d, scale, *, eps=1e-5, block_rows=256, interpret=False):
    """x2d: [R, d]; scale: [d] -> [R, d]."""
    R, d = x2d.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(R // block_rows,),
        # scale travels as [1, d]: a whole-array (1, d) block stays legal
        # when vmap adds a leading axis (a [d] block would not)
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, d), x2d.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2d, scale.reshape(1, d))
