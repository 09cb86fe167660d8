"""Jit'd wrapper for the fused RMSNorm kernel (model layout [..., d]).

The kernel has no reverse-mode rule of its own, so the op is a
``custom_vjp``: the forward runs the Pallas kernel and the backward is the
VJP of :func:`rmsnorm_ref` (identical math), which lets the FL client
steps train through it on TPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _forward(x, scale, eps, interpret):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    R = x2d.shape[0]
    # rows pad to the dtype's sublane tile (8 rows of f32), so every row
    # block is tile-aligned; padded zero rows normalise to zero and are cut
    sub = 32 // x.dtype.itemsize
    r_pad = -(-R // sub) * sub
    if r_pad != R:
        x2d = jnp.pad(x2d, ((0, r_pad - R), (0, 0)))
    br = 256
    while r_pad % br:
        br //= 2
    out = rmsnorm(x2d, scale, eps=eps, block_rows=br, interpret=interpret)
    return out[:R].reshape(shape)


def _reference(x, scale, eps):
    return rmsnorm_ref(x.reshape(-1, x.shape[-1]), scale,
                       eps).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rmsnorm(x, scale, eps, interpret):
    return _forward(x, scale, eps, interpret)


def _rmsnorm_fwd(x, scale, eps, interpret):
    return _forward(x, scale, eps, interpret), (x, scale)


def _rmsnorm_bwd(eps, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(_reference, eps=eps), *res)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm_op(x, scale, *, eps=1e-5, interpret=None):
    if interpret is None:
        interpret = not _on_tpu()
    return _rmsnorm(x, scale, eps, interpret)
