"""Early-exit decoder-only transformer — the third registered
:class:`~repro.models.family.ModelFamily` (``model_family="transformer"``).

The on-device-LLM variant of the paper's §4.2 dual-selection story: depth
is the submodel axis.  The global model is a stack of ``N_BLOCKS``
pre-norm decoder blocks with one next-token exit head per block; submodel
m = embedding stem + blocks[:m+1] + exit heads <= m, exactly the DR-FL
depth-prefix contract, so the whole FL stack (bucketed-vmap executor,
stacked Pallas aggregation, Eq. 5/7 cost model, sync/async engine,
checkpoint/resume, energy scenarios) runs it through the generic
:class:`~repro.models.family.LayerwiseFamily` machinery.

Kernel routing — the block's normalisation and attention go through the
repo's Pallas ops/ref parity contract (``repro.kernels.rmsnorm``,
``repro.kernels.flash_attention``):

* on TPU the compiled Pallas kernels run on the traced path;
* elsewhere the pure-jnp oracles (``rmsnorm_ref`` / ``attention_ref``)
  run DIRECTLY — identical math to the kernels (that is the parity
  contract ``tests/test_kernels.py`` enforces in interpret mode), without
  paying the Pallas interpreter in the hot path;
* tests force either side via :func:`kernel_mode` and assert the two
  forwards agree (interpret-mode Pallas vs ref on CPU).

No-retrace heterogeneous depth: unlike the cnn/mlp step (one jitted
program per static ``model_idx``), this family's DR-FL step is a SINGLE
jitted program taking a *traced* ``model_idx``.  The forward always runs
full depth; a per-exit weight vector (1.0 at the held depth, 0.3 for
shallower exits, exactly 0.0 deeper — the same BranchyNet weighting and
normalisation as ``LayerwiseFamily._drfl_loss``) masks the joint CE, so
gradients past the held prefix are exactly zero and the returned delta is
zero-filled for layer-aligned aggregation, while every submodel reuses
one compiled program (``tests/test_family_contract.py`` pins the
single-compilation property).

Data: :meth:`TransformerFamily.make_dataset` serves the synthetic
next-token corpus (:func:`repro.data.synthetic.synthetic_token_dataset`),
framing next-token prediction as classification over ``num_classes``
(= vocab) so ``run_simulation`` works offline with the stack's CE loss,
per-exit accuracy evaluation and label-Dirichlet sharding unchanged;
``cfg.hw`` doubles as the sequence length.
"""
from __future__ import annotations

import contextlib
import math
from typing import List

import jax
import jax.numpy as jnp

from repro.models.family import (LayerwiseFamily, cross_entropy,
                                 register_family)
from repro.models.layers import (apply_rope, dense_apply, dense_bias_init,
                                 dense_init, embed_init, gelu_mlp_apply,
                                 gelu_mlp_init, rmsnorm_init)

N_BLOCKS = 4              # one exit head per block = 4 submodels (paper M)
BASE_WIDTH = 128          # d_model at width_mult=1.0
N_HEADS = 4
MLP_RATIO = 4             # hidden = MLP_RATIO * d
ROPE_THETA = 10000.0


def _width(width_mult: float) -> int:
    """d_model: multiple of 2*N_HEADS so every head splits evenly for
    RoPE's half-dim rotation."""
    step = 2 * N_HEADS
    d = max(32, int(BASE_WIDTH * width_mult))
    return ((d + step - 1) // step) * step


# ---------------------------------------------------------------------------
# kernel dispatch (Pallas ops on TPU, identical-math oracles elsewhere)
# ---------------------------------------------------------------------------

_KERNEL_MODE = None       # None = auto; "pallas" | "ref" force one side


@contextlib.contextmanager
def kernel_mode(mode):
    """Force the block's kernel dispatch while tracing: ``"pallas"`` runs
    the Pallas ops (interpret mode off-TPU), ``"ref"`` the pure-jnp
    oracles.  Test-only: the choice is baked in at TRACE time, so only
    fresh traces (eager calls / new jits) see the override — the family's
    cached step/eval programs keep whatever the engine traced with."""
    global _KERNEL_MODE
    if mode not in ("pallas", "ref"):
        raise ValueError(f"kernel_mode must be 'pallas' or 'ref', "
                         f"got {mode!r}")
    prev = _KERNEL_MODE
    _KERNEL_MODE = mode
    try:
        yield
    finally:
        _KERNEL_MODE = prev


def _use_pallas() -> bool:
    if _KERNEL_MODE is not None:
        return _KERNEL_MODE == "pallas"
    return jax.default_backend() == "tpu"


def _largest_pow2_leq(n: int, cap: int) -> int:
    b = 1
    while b * 2 <= min(n, cap):
        b *= 2
    return b


def _rmsnorm(p, h):
    """rmsnorm over the trailing dim: Pallas op on TPU, oracle elsewhere."""
    if _use_pallas():
        from repro.kernels.rmsnorm import rmsnorm_op
        return rmsnorm_op(h, p["scale"])
    from repro.kernels.rmsnorm import rmsnorm_ref
    return rmsnorm_ref(h.reshape(-1, h.shape[-1]),
                       p["scale"]).reshape(h.shape)


def _attend(q, k, v):
    """Causal self-attention, model layout [B, S, H, D]."""
    if _use_pallas():
        from repro.kernels.flash_attention import flash_attention
        blk = _largest_pow2_leq(q.shape[1], 128)
        return flash_attention(q, k, v, causal=True, block_q=blk,
                               block_k=blk)
    from repro.kernels.flash_attention.ops import attention_ref_bshd
    return attention_ref_bshd(q, k, v, causal=True)


# ---------------------------------------------------------------------------
# model (canonical {"stem", "stages", "exits"} layer-wise tree)
# ---------------------------------------------------------------------------


def init(key, num_classes: int = 10, width_mult: float = 1.0, hw: int = 32):
    """Canonical layer-wise tree: stem (token embedding over a
    ``num_classes``-sized vocab), N_BLOCKS pre-norm decoder blocks as
    stages, one rmsnorm + linear next-token head per stage.  ``hw`` (the
    sequence length) is positional-encoding-free at init — positions are
    rotary, applied at trace time."""
    d = _width(width_mult)
    f = MLP_RATIO * d
    ks = jax.random.split(key, 1 + 2 * N_BLOCKS)
    it = iter(ks)
    params = {
        "stem": {"embed": embed_init(next(it), num_classes, d, jnp.float32)},
        "stages": [],
        "exits": [],
    }
    for _ in range(N_BLOCKS):
        bk = jax.random.split(next(it), 5)
        params["stages"].append({
            "attn_norm": rmsnorm_init(d, jnp.float32),
            "attn": {
                "wq": dense_init(bk[0], d, d, jnp.float32),
                "wk": dense_init(bk[1], d, d, jnp.float32),
                "wv": dense_init(bk[2], d, d, jnp.float32),
                "wo": dense_init(bk[3], d, d, jnp.float32,
                                 scale=1.0 / math.sqrt(d)),
            },
            "mlp_norm": rmsnorm_init(d, jnp.float32),
            "mlp": gelu_mlp_init(bk[4], d, f, jnp.float32),
        })
        params["exits"].append({
            "norm": rmsnorm_init(d, jnp.float32),
            "head": dense_bias_init(next(it), d, num_classes, jnp.float32,
                                    scale=1.0 / math.sqrt(d)),
        })
    return params


def num_submodels() -> int:
    return N_BLOCKS


def _attention(bp, h):
    B, S, d = h.shape
    hd = d // N_HEADS
    q = dense_apply(bp["wq"], h).reshape(B, S, N_HEADS, hd)
    k = dense_apply(bp["wk"], h).reshape(B, S, N_HEADS, hd)
    v = dense_apply(bp["wv"], h).reshape(B, S, N_HEADS, hd)
    pos = jnp.arange(S)
    q = apply_rope(q, pos, ROPE_THETA)
    k = apply_rope(k, pos, ROPE_THETA)
    o = _attend(q, k, v)
    return dense_apply(bp["wo"], o.reshape(B, S, d))


def _block(bp, h):
    h = h + _attention(bp["attn"], _rmsnorm(bp["attn_norm"], h))
    return h + gelu_mlp_apply(bp["mlp"], _rmsnorm(bp["mlp_norm"], h))


def _exit_head(ep, h):
    """Next-token logits at the LAST position (the window's label slot)."""
    return dense_apply(ep["head"], _rmsnorm(ep["norm"], h[:, -1, :]))


def apply(params, x, model_idx: int):
    """x: [B, S] int32 tokens -> logits at exit ``model_idx``."""
    h = jnp.take(params["stem"]["embed"]["emb"], x, axis=0)
    for si in range(model_idx + 1):
        h = _block(params["stages"][si], h)
    return _exit_head(params["exits"][model_idx], h)


def apply_all_exits(params, x) -> List[jnp.ndarray]:
    """Logits from every exit held by ``params`` (truncated trees ok)."""
    h = jnp.take(params["stem"]["embed"]["emb"], x, axis=0)
    outs = []
    for si in range(len(params["stages"])):
        h = _block(params["stages"][si], h)
        outs.append(_exit_head(params["exits"][si], h))
    return outs


def flops_per_sample(model_idx: int, image_hw: int = 32,
                     width_mult: float = 1.0, num_classes: int = 10) -> float:
    """Analytic forward FLOPs for Model_{idx+1}; ``image_hw`` is the
    sequence length (the FL stack's one spatial knob)."""
    d = _width(width_mult)
    f = MLP_RATIO * d
    S = image_hw
    per_block = (4 * 2.0 * S * d * d        # q/k/v/o projections
                 + 2 * 2.0 * S * S * d      # scores + weighted values
                 + 2.0 * S * (d * f + f * d))  # GELU MLP in + out
    return (model_idx + 1) * per_block + 2.0 * d * num_classes


# ---------------------------------------------------------------------------
# the family
# ---------------------------------------------------------------------------


class TransformerFamily(LayerwiseFamily):
    """Early-exit decoder as a pluggable family
    (``model_family="transformer"``).

    DR-FL (depth-prefix) only, like the MLP: width-slicing attention heads
    is a different baseline design, so
    :class:`repro.fl.spec.SimulationSpec` rejects HeteroFL/ScaleFL with
    this family up front."""

    name = "transformer"
    supported_methods = ("drfl",)
    ref_hw = 32          # paper-scale sequence length (cost calibration)

    def init(self, key, num_classes: int = 10, width_mult: float = 1.0,
             hw: int = 32):
        return init(key, num_classes, width_mult=width_mult, hw=hw)

    def num_submodels(self) -> int:
        return num_submodels()

    def apply_all_exits(self, params, x):
        return apply_all_exits(params, x)

    def flops_per_sample(self, model_idx: int, image_hw: int = 32,
                         width_mult: float = 1.0) -> float:
        return flops_per_sample(model_idx, image_hw, width_mult)

    def make_dataset(self, n: int, num_classes: int = 10, hw: int = 32,
                     noise: float = 1.0, seed: int = 0):
        from repro.data.synthetic import synthetic_token_dataset
        return synthetic_token_dataset(n, num_classes, seq_len=hw,
                                       noise=noise, seed=seed)

    # -- no-retrace heterogeneous depth -----------------------------------
    def _masked_drfl_loss(self, params, x, y, model_idx):
        """Full-depth forward, per-exit weights from the TRACED held depth:
        1.0 at ``model_idx``, 0.3 shallower, exactly 0.0 deeper — the same
        joint-CE weighting/normalisation as ``_drfl_loss`` on a truncated
        tree, but with zero-weight (hence exactly-zero-gradient) deep
        exits instead of absent ones."""
        outs = self.apply_all_exits(params, x)
        ces = jnp.stack([cross_entropy(o, y) for o in outs])
        idx = jnp.arange(len(outs))
        w = jnp.where(idx == model_idx, 1.0,
                      jnp.where(idx < model_idx, 0.3, 0.0))
        return jnp.sum(w * ces) / (1.0 + 0.3 * model_idx)

    def _step_fn(self, method: str):
        if method != "drfl":
            return super()._step_fn(method)
        key = ("step", method)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn

        # jaxlint: allow(retrace-hazard) -- memoised in self._jit_cache keyed by (step, method); model_idx is TRACED so all submodels share one compilation
        @jax.jit
        def fn(params, x, y, model_idx, lr: float = 0.05):
            loss, grads = jax.value_and_grad(
                lambda p: self._masked_drfl_loss(p, x, y, model_idx))(params)
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, loss

        self._jit_cache[key] = fn
        return fn


register_family(TransformerFamily())
