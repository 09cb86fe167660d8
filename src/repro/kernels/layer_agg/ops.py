"""Jit'd wrapper + pytree adapter for the layer-agg kernel.

``layer_agg_op`` pads the ``[N, R, D]`` stack to the kernel's tiling (R to
a multiple of the sublane tile, D to a multiple of 128, N to the client
block when N is tiled) and picks the tiles from a VMEM budget
(:func:`plan_tiles`).
``aggregate_stacked_leaf`` flattens every stacked ``[L, ...]`` leaf of N
client update pytrees into one such call, then scatters results back — so
the whole of DR-FL Step 2 for a scanned transformer is a handful of fused
kernel launches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.layer_agg.layer_agg import layer_agg

#: bytes of one update tile; double-buffered by the pipeline it stays well
#: inside v5e's 16 MiB default scoped VMEM next to the small wm/out tiles
TILE_BYTES = 4 << 20
#: clients reduced per grid step before the client axis is tiled (a
#: multiple of 128, the lane tiling of the transposed mask block)
MAX_CLIENT_BLOCK = 512
#: accumulator rows x lanes kept per step (32 vregs of f32)
MAX_ACC_ELEMS = 32 * 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def plan_tiles(N: int, R: int, D: int, itemsize: int = 4,
               block_d: int = 2048):
    """``(bn, br, bd)`` and the padded ``(N, R, D)`` for an ``[N, R, D]``
    update stack: ``bd`` a multiple of 128 dividing the padded D (at most
    ``block_d``), ``br`` a power-of-two multiple of the dtype's sublane
    tile (8 rows of f32) dividing the padded R, ``bn`` the whole N up to
    :data:`MAX_CLIENT_BLOCK`; each grows only while the update tile stays
    within :data:`TILE_BYTES`."""
    sub = 32 // itemsize
    bn = N if N <= MAX_CLIENT_BLOCK else MAX_CLIENT_BLOCK
    n_pad = _round_up(N, bn)
    r_pad = _round_up(max(R, 1), sub)
    d_pad = _round_up(max(D, 1), 128)
    bd = 128
    for cand in range(128, min(max(block_d, 128), d_pad) + 1, 128):
        if d_pad % cand == 0 and bn * sub * cand * itemsize <= TILE_BYTES:
            bd = cand
    br = sub
    while (r_pad % (2 * br) == 0 and 2 * br * bd <= MAX_ACC_ELEMS
           and bn * 2 * br * bd * itemsize <= TILE_BYTES):
        br *= 2
    return (bn, br, bd), (n_pad, r_pad, d_pad)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def layer_agg_op(updates, masks, weights, *, block_d=2048, interpret=None):
    """updates: [N, R, D]; masks: [N, R]; weights: [N] -> [R, D] float32."""
    if interpret is None:
        interpret = not _on_tpu()
    N, R, D = updates.shape
    (bn, br, bd), (n_pad, r_pad, d_pad) = plan_tiles(
        N, R, D, updates.dtype.itemsize, block_d)
    wm_t = (weights[:, None].astype(jnp.float32)
            * masks.astype(jnp.float32)).T                        # [R, N]
    if (n_pad, r_pad, d_pad) != (N, R, D):
        updates = jnp.pad(updates, ((0, n_pad - N), (0, r_pad - R),
                                    (0, d_pad - D)))
        wm_t = jnp.pad(wm_t, ((0, r_pad - R), (0, n_pad - N)))
    out = layer_agg(updates, wm_t, br=br, bd=bd, bn=bn,
                    interpret=interpret)
    return out[:R, :D]


def aggregate_stacked_leaf(global_leaf, client_leaves, client_masks, weights,
                           interpret=None):
    """global_leaf: [L, ...]; client_leaves: list of [L, ...];
    client_masks: list of [L] (or broadcastable).  Returns updated leaf."""
    L = global_leaf.shape[0]
    D = int(global_leaf.size // L)
    U = jnp.stack([c.reshape(L, D) for c in client_leaves])      # [N,L,D]
    M = jnp.stack([jnp.broadcast_to(m.reshape(m.shape[0], -1)[:, 0], (L,))
                   for m in client_masks])                        # [N,L]
    w = jnp.asarray(weights, jnp.float32)
    avg = layer_agg_op(U, M, w, interpret=interpret)              # [L,D]
    return (global_leaf.astype(jnp.float32)
            + avg.reshape(global_leaf.shape)).astype(global_leaf.dtype)
