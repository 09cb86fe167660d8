"""Jit'd public wrapper: model-layout in/out + CPU interpret fallback.

The kernel has no reverse-mode rule of its own, so the op is a
``custom_vjp``: the forward runs the Pallas kernel and the backward is the
VJP of :func:`attention_ref` (identical math), which lets the FL client
steps and the QMIX set mixer train through it on TPU."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.flash_attention.ref import attention_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _to_bhsd(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bhsd(o, B, H):
    _, S, D = o.shape
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def attention_ref_bshd(q, k, v, *, causal=True, window=0):
    """:func:`attention_ref` in the model layout q [B, Sq, Hq, D], k/v
    [B, Sk, Hkv, D] -> [B, Sq, Hq, D]."""
    o = attention_ref(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), causal=causal,
                      window=window)
    return _from_bhsd(o, q.shape[0], q.shape[2])


def _forward(q, k, v, causal, window, block_q, block_k, interpret):
    o = flash_attention_bhsd(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
                             causal=causal, window=window, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    return _from_bhsd(o, q.shape[0], q.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    return _forward(q, k, v, causal, window, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    return (_forward(q, k, v, causal, window, block_q, block_k, interpret),
            (q, k, v))


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(attention_ref_bshd, causal=causal,
                                       window=window), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None):
    """Model layout: q [B, Sq, Hq, D]; k/v [B, Sk, Hkv, D] -> [B, Sq, Hq, D].

    ``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere.
    """
    if interpret is None:
        interpret = not _on_tpu()
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)
