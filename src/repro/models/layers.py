"""Core neural-net primitives (pure JAX, no flax).

Parameters are plain nested dicts of ``jnp.ndarray``.  Stacked ("scanned")
layer parameters carry a leading ``[L, ...]`` axis produced by ``vmap`` over
per-layer PRNG keys — see :mod:`repro.models.transformer`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

Params = dict

# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return {"w": _normal(key, (d_in, d_out), scale, dtype)}


def dense_bias_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    p = dense_init(key, d_in, d_out, dtype, scale)
    p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embed_init(key, vocab: int, d: int, dtype):
    return {"emb": _normal(key, (vocab, d), 1.0, dtype)}


# ---------------------------------------------------------------------------
# normalisation
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    out = (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    # dtype barrier: without it XLA hoists the f32 internals above the SPMD
    # partitioner's resharding point and the residual-stream all-gathers /
    # all-reduces move FULL-PRECISION tensors (measured 2.8 TB f32/step on
    # yi-34b train_4k; bf16 halves it).  See EXPERIMENTS.md §Perf.
    return jax.lax.optimization_barrier(out)


def layernorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: broadcastable to [..., S] (int)."""
    freqs = rope_freqs(x.shape[-1], theta)                     # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, causal / sliding-window / cross, cached decode)
# ---------------------------------------------------------------------------


def attention_init(key, cfg, dtype, *, cross: bool = False):
    d, nh, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 6)
    mk = dense_bias_init if cfg.attn_bias else dense_init
    p = {
        "wq": mk(ks[0], d, nh * hd, dtype),
        "wk": mk(ks[1], d, nkv * hd, dtype),
        "wv": mk(ks[2], d, nkv * hd, dtype),
        "wo": mk(ks[3], nh * hd, d, dtype, scale=1.0 / math.sqrt(nh * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def gqa_attend(
    q: jnp.ndarray,            # [B, Sq, Hq, D]
    k: jnp.ndarray,            # [B, Sk, Hkv, D]
    v: jnp.ndarray,            # [B, Sk, Hkv, D]
    *,
    causal: bool,
    window: int = 0,
    q_offset=0,                # scalar or [B]; absolute position of q[0]
    kv_len=None,               # scalar/[B]: #valid cache entries (decode)
) -> jnp.ndarray:
    """Grouped-query attention.

    Default: grouped einsum (no repeated KV in HBM).  Under the
    ``repeat_kv`` sharding policy the KV heads ARE materialised to Hq so the
    score einsum contracts only the head_dim — on TP meshes where Hkv does
    not divide the model axis, the grouped form makes GSPMD partially
    contract the KV-head axis and ALL-REDUCE full [Sq,Sk] score tensors
    (measured 2.7 TB/step on yi-34b train_4k; see EXPERIMENTS.md §Perf)."""
    from repro.sharding.rules import get_sharding_policy
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    q_pos = jnp.asarray(q_offset)[..., None] + jnp.arange(Sq)     # [..., Sq]
    k_pos = jnp.arange(Sk)                                        # [Sk]
    mask = jnp.ones((Sq, Sk), bool) if q_pos.ndim == 1 else None
    qp = q_pos[..., :, None]                                      # [(B,)Sq,1]
    kp = k_pos[None, :]
    valid = jnp.ones_like(qp * 0 + kp, dtype=bool) if mask is None else mask
    if causal:
        valid = valid & (kp <= qp)
    if window:
        valid = valid & (kp > qp - window)
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        kl = kl[..., None, None] if kl.ndim == 1 else kl
        valid = valid & (kp < kl)
    while valid.ndim < 3:
        valid = valid[None]
    # valid: [B or 1, Sq, Sk]

    if get_sharding_policy().get("repeat_kv") and G > 1:
        # materialise repeated KV heads: the score einsum then has the
        # (padded, shardable) Hq axis as a pure batch dim
        from repro.sharding.rules import attn_head_shard
        kr = jnp.repeat(k, G, axis=2)
        vr = jnp.repeat(v, G, axis=2)
        q, kr, vr = attn_head_shard(q, kr, vr)
        # bf16 operands, fp32 MXU accumulation: collectives/reshards of
        # q/k/v stay half-width (the fp32 upcast used to happen BEFORE the
        # KV all-gather — measured 258 GB/step of f32 gathers on yi-34b)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), vr,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    qg = q.reshape(B, Sq, Hkv, G, D)
    # scores: [B, Hkv, G, Sq, Sk]; bf16 operands, fp32 accumulation
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def gqa_attend_chunked(
    q: jnp.ndarray,            # [B, Sq, Hq, D]
    k: jnp.ndarray,            # [B, Sk, Hkv, D]
    v: jnp.ndarray,
    *,
    causal: bool,
    window: int = 0,
    chunk: int = 512,
) -> jnp.ndarray:
    """Online-softmax attention scanning over KV blocks — an XLA-level
    flash attention.  Never materialises the [Sq, Sk] score matrix: peak
    per-step memory is [B, H, Sq, chunk].  Numerically equivalent to
    :func:`gqa_attend` (same fp32 accumulation)."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if Sk % chunk or Sk <= chunk:
        return gqa_attend(q, k, v, causal=causal, window=window)
    G = Hq // Hkv
    nblk = Sk // chunk
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D)
    kb = jnp.moveaxis(k.reshape(B, nblk, chunk, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, chunk, Hkv, D), 1, 0)
    q_pos = jnp.arange(Sq)[:, None]

    def body(carry, xs):
        acc, m, l = carry
        kj, vj, j = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        k_pos = j * chunk + jnp.arange(chunk)[None, :]
        valid = jnp.ones((Sq, chunk), bool)
        if causal:
            valid &= k_pos <= q_pos
        if window:
            valid &= k_pos > q_pos - window
        s = jnp.where(valid[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vj.dtype),
                                       vj, preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (kb, vb, jnp.arange(nblk)))
    o = acc / jnp.maximum(l, 1e-30)
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, Hq, D).astype(q.dtype)


def attention_apply(
    p: Params,
    cfg,
    x: jnp.ndarray,                 # [B, S, d]
    positions: jnp.ndarray,         # [B, S] or [S]
    *,
    causal: bool = True,
    window: int = 0,
    cache: Optional[Params] = None,  # decode: {'k','v','pos'}
    kv_src: Optional[jnp.ndarray] = None,  # cross-attn source states
    use_pallas: bool = False,
    attn_chunk: int = 0,
    norm_eps: float = 1e-5,
):
    """Returns (out, new_cache)."""
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = _split_heads(dense_apply(p["wq"], x), nh, hd)
    src = x if kv_src is None else kv_src
    k = _split_heads(dense_apply(p["wk"], src), nkv, hd)
    v = _split_heads(dense_apply(p["wv"], src), nkv, hd)
    if "q_norm" in p:
        q = rmsnorm_apply(p["q_norm"], q, norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, norm_eps)
    if kv_src is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if cache is None:
        from repro.sharding.rules import attn_seq_shard
        q, k, v = attn_seq_shard(q, k, v)

    new_cache = None
    if cache is not None and kv_src is None:
        # single-token decode append; ring buffer when the cache is
        # window-sized (slot order is irrelevant post-RoPE: keys carry their
        # absolute positions, softmax is permutation-invariant).
        pos = cache["pos"]
        clen = cache["k"].shape[1]
        widx = jax.lax.rem(pos, clen)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), widx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), widx, axis=1)
        new_cache = {"k": ck, "v": cv, "pos": pos + x.shape[1]}
        o = gqa_attend(q, ck, cv, causal=False, window=0,
                       q_offset=pos, kv_len=jnp.minimum(pos + x.shape[1], clen))
    elif cache is not None:  # cross-attention with precomputed static cache
        o = gqa_attend(q, cache["k"], cache["v"], causal=False)
        new_cache = cache
    else:
        if use_pallas and kv_src is None and causal:
            from repro.kernels.flash_attention import ops as fa_ops
            o = fa_ops.flash_attention(q, k, v, causal=True, window=window)
        elif attn_chunk and kv_src is None:
            o = gqa_attend_chunked(q, k, v, causal=causal, window=window,
                                   chunk=attn_chunk)
        else:
            o = gqa_attend(q, k, v, causal=causal and kv_src is None, window=window)
    out = dense_apply(p["wo"], o.reshape(x.shape[:-1] + (nh * hd,)))
    return out, new_cache


def make_kv_cache(cfg, batch: int, length: int, dtype) -> Params:
    return {
        "k": jnp.zeros((batch, length, cfg.num_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, length, cfg.num_kv_heads, cfg.hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d: int, f: int, dtype, bias: bool = False):
    ks = jax.random.split(key, 3)
    mk = dense_bias_init if bias else dense_init
    return {
        "w_gate": mk(ks[0], d, f, dtype),
        "w_up": mk(ks[1], d, f, dtype),
        "w_down": mk(ks[2], f, d, dtype, scale=1.0 / math.sqrt(f)),
    }


def swiglu_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return dense_apply(p["w_down"], jax.nn.silu(dense_apply(p["w_gate"], x)) * dense_apply(p["w_up"], x))


def gelu_mlp_init(key, d: int, f: int, dtype, bias: bool = True):
    ks = jax.random.split(key, 2)
    mk = dense_bias_init if bias else dense_init
    return {"w_in": mk(ks[0], d, f, dtype), "w_out": mk(ks[1], f, d, dtype, scale=1.0 / math.sqrt(f))}


def gelu_mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return dense_apply(p["w_out"], jax.nn.gelu(dense_apply(p["w_in"], x)))


def mlp_init(key, net_dims, dtype=jnp.float32):
    """Generic MLP used by the MARL nets: net_dims = [in, h1, ..., out]."""
    ks = jax.random.split(key, len(net_dims) - 1)
    return {f"l{i}": dense_bias_init(ks[i], net_dims[i], net_dims[i + 1], dtype)
            for i in range(len(net_dims) - 1)}


def mlp_apply(p: Params, x: jnp.ndarray, act=jax.nn.relu) -> jnp.ndarray:
    n = len(p)
    for i in range(n):
        x = dense_apply(p[f"l{i}"], x)
        if i < n - 1:
            x = act(x)
    return x


# ---------------------------------------------------------------------------
# GRU cell (MARL agents, paper Fig. 3)
# ---------------------------------------------------------------------------


def gru_init(key, d_in: int, d_h: int, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    return {
        "wx": dense_bias_init(ks[0], d_in, 3 * d_h, dtype),
        "wh": dense_init(ks[1], d_h, 3 * d_h, dtype),
    }


def gru_apply(p: Params, h: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    gx = dense_apply(p["wx"], x)
    gh = dense_apply(p["wh"], h)
    xr, xz, xn = jnp.split(gx, 3, axis=-1)
    hr, hz, hn = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    return (1.0 - z) * n + z * h
