"""Paper-faithful backbone: ResNet-18-style CNN with 4 early exits.

DR-FL (§5.1.1): "The ResNet-18 model serves as the backbone. Each block of
the ResNet-18 model is accompanied by a bottleneck and classifier, resulting
in the creation of four distinct layer-wise models" (Models 1–4).

Model_m = stem + stages[0..m] + exit[m]  (depth-prefix submodel).
Exit head = 1x1 bottleneck conv + global-avg-pool + linear classifier.

Parameters are a dict with per-stage subtrees so the DR-FL layer-wise
aggregation can mask whole stages; exits are aggregated only across clients
training the same exit.
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

STAGE_CHANNELS = (64, 128, 256, 512)
BLOCKS_PER_STAGE = 2


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * math.sqrt(2.0 / fan_in))


# Under vmap with per-client kernels (the bucketed client-update executor),
# lax.conv lowers to a grouped convolution, which XLA CPU executes on a
# naive non-Eigen path — up to ~10x slower per FLOP at paper widths.  The
# patches formulation below turns the same conv into static slices + an
# einsum; vmapped, that is a batched GEMM, which XLA CPU runs at BLAS
# speed.  Trace-time flag: only the bucket program flips it (and only on
# CPU); everything else keeps the cuDNN/Eigen/MXU-friendly lax.conv.
_CONV_VIA_PATCHES = False


@contextlib.contextmanager
def conv_via_patches():
    global _CONV_VIA_PATCHES
    prev = _CONV_VIA_PATCHES
    _CONV_VIA_PATCHES = True
    try:
        yield
    finally:
        _CONV_VIA_PATCHES = prev


def _conv_patches(x, w, stride=1):
    """SAME conv as shifted slices + einsum (identical math to lax.conv up
    to float reduction order)."""
    B, H, W, _ = x.shape
    kh, kw, _, _ = w.shape
    ho = -(-H // stride)
    wo = -(-W // stride)
    ph = max((ho - 1) * stride + kh - H, 0)
    pw = max((wo - 1) * stride + kw - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    rows = []
    for i in range(kh):
        cols = []
        for j in range(kw):
            cols.append(xp[:, i:i + stride * (ho - 1) + 1:stride,
                           j:j + stride * (wo - 1) + 1:stride, :])
        rows.append(jnp.stack(cols, axis=-2))
    patches = jnp.stack(rows, axis=-3)            # [B, ho, wo, kh, kw, C]
    return jnp.einsum("bhwijc,ijco->bhwo", patches, w)


def _conv(x, w, stride=1):
    if _CONV_VIA_PATCHES:
        return _conv_patches(x, w, stride)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _gn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _groupnorm(p, x, groups=8):
    # GroupNorm instead of BatchNorm: batch-size independent (FL clients train
    # with small local batches; avoids running-stat aggregation headaches).
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:        # width-sliced channel counts need not divide 8
        g -= 1
    xg = x.reshape(B, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(B, H, W, C) * p["scale"] + p["bias"]


def _basic_block_init(key, cin, cout, stride):
    ks = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(ks[0], 3, 3, cin, cout), "gn1": _gn_init(cout),
        "conv2": _conv_init(ks[1], 3, 3, cout, cout), "gn2": _gn_init(cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[2], 1, 1, cin, cout)
    return p


def _basic_block(p, x, stride):
    h = jax.nn.relu(_groupnorm(p["gn1"], _conv(x, p["conv1"], stride)))
    h = _groupnorm(p["gn2"], _conv(h, p["conv2"]))
    sc = _conv(x, p["proj"], stride) if "proj" in p else x
    return jax.nn.relu(h + sc)


def init(key, num_classes: int = 10, in_channels: int = 3,
         width_mult: float = 1.0):
    """width_mult < 1 slims every stage (CPU-budget benchmark runs keep the
    4-stage / 4-exit ResNet-18 topology but shrink channels)."""
    chans = [max(8, int(c * width_mult)) for c in STAGE_CHANNELS]
    ks = jax.random.split(key, 2 + len(chans) * (BLOCKS_PER_STAGE + 1))
    it = iter(ks)
    c0 = chans[0]
    params = {
        "stem": {"conv": _conv_init(next(it), 3, 3, in_channels, c0),
                 "gn": _gn_init(c0)},
        "stages": [],
        "exits": [],
    }
    cin = c0
    for si, cout in enumerate(chans):
        blocks = []
        for bi in range(BLOCKS_PER_STAGE):
            stride = 2 if (bi == 0 and si > 0) else 1
            blocks.append(_basic_block_init(next(it), cin, cout, stride))
            cin = cout
        params["stages"].append(blocks)
        kb = next(it)
        k1, k2 = jax.random.split(kb)
        bott = max(16, cout // 2)
        params["exits"].append({
            "bottleneck": _conv_init(k1, 1, 1, cout, bott),
            "gn": _gn_init(bott),
            "w": jax.random.normal(k2, (bott, num_classes)) / math.sqrt(bott),
            "b": jnp.zeros((num_classes,)),
        })
    return params


def num_submodels() -> int:
    return len(STAGE_CHANNELS)


def _exit_head(p, x):
    h = jax.nn.relu(_groupnorm(p["gn"], _conv(x, p["bottleneck"])))
    h = h.mean(axis=(1, 2))
    return h @ p["w"] + p["b"]


def apply(params, x, model_idx: int):
    """x: [B,32,32,3] -> logits at exit ``model_idx`` (0..3).

    ``model_idx`` selects the depth-prefix submodel (Model_{idx+1}).
    Static python int — each submodel is its own (tiny) jitted program.
    """
    h = jax.nn.relu(_groupnorm(params["stem"]["gn"], _conv(x, params["stem"]["conv"])))
    for si in range(model_idx + 1):
        for bi, bp in enumerate(params["stages"][si]):
            stride = 2 if (bi == 0 and si > 0) else 1
            h = _basic_block(bp, h, stride)
    return _exit_head(params["exits"][model_idx], h)


def apply_all_exits(params, x):
    """Returns logits from every exit held by ``params`` (supports truncated
    / width-sliced submodel trees as well as the full global model)."""
    h = jax.nn.relu(_groupnorm(params["stem"]["gn"], _conv(x, params["stem"]["conv"])))
    outs = []
    for si in range(len(params["stages"])):
        for bi, bp in enumerate(params["stages"][si]):
            stride = 2 if (bi == 0 and si > 0) else 1
            h = _basic_block(bp, h, stride)
        outs.append(_exit_head(params["exits"][si], h))
    return outs


def submodel_param_tree(params, model_idx: int):
    """The pytree a Model_{idx+1} client actually holds/trains."""
    return {
        "stem": params["stem"],
        "stages": params["stages"][:model_idx + 1],
        "exits": [params["exits"][model_idx]],
    }


def submodel_size_bytes(params, model_idx: int) -> int:
    tree = submodel_param_tree(params, model_idx)
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def flops_per_sample(model_idx: int, image_hw: int = 32,
                     width_mult: float = 1.0) -> float:
    """Rough analytic forward FLOPs for Model_{idx+1} (energy model input)."""
    chans = [max(8, int(c * width_mult)) for c in STAGE_CHANNELS]
    total, hw, cin = 0.0, image_hw, 3
    total += 2 * 9 * cin * chans[0] * hw * hw
    cin = chans[0]
    for si in range(model_idx + 1):
        cout = chans[si]
        stride = 2 if si > 0 else 1
        hw = hw // stride
        for bi in range(BLOCKS_PER_STAGE):
            total += 2 * 9 * cin * cout * hw * hw
            total += 2 * 9 * cout * cout * hw * hw
            cin = cout
    total += 2 * cin * max(16, cin // 2) * hw * hw
    return total


# ---------------------------------------------------------------------------
# ModelFamily adapter: the registered default family ("cnn")
# ---------------------------------------------------------------------------


from repro.models.family import LayerwiseFamily, register_family  # noqa: E402


class CnnFamily(LayerwiseFamily):
    """The paper's multi-exit ResNet-18 as a pluggable :class:`ModelFamily`.

    The only family that supports all three FL methods: HeteroFL /
    ScaleFL submodels are structural channel-prefix slices of the conv
    tree (:mod:`repro.core.baselines`)."""

    name = "cnn"
    supported_methods = ("drfl", "heterofl", "scalefl")

    def init(self, key, num_classes: int = 10, width_mult: float = 1.0,
             hw: int = 32):
        # parameters are image-size independent; ``hw`` only matters for
        # the analytic FLOP model
        return init(key, num_classes, width_mult=width_mult)

    def num_submodels(self) -> int:
        return num_submodels()

    def apply_all_exits(self, params, x):
        return apply_all_exits(params, x)

    def flops_per_sample(self, model_idx: int, image_hw: int = 32,
                         width_mult: float = 1.0) -> float:
        return flops_per_sample(model_idx, image_hw, width_mult)

    def submodel_params(self, method: str, global_params, model_idx: int):
        from repro.core.baselines import (WIDTH_LEVELS, scalefl_submodel,
                                          width_slice_cnn)
        if method == "heterofl":
            return width_slice_cnn(global_params, WIDTH_LEVELS[model_idx])
        if method == "scalefl":
            return scalefl_submodel(global_params, model_idx)
        return super().submodel_params(method, global_params, model_idx)

    def bucket_trace_context(self):
        # vmapped lax.conv with per-client kernels = grouped conv, which
        # XLA CPU runs ~10x off BLAS speed at paper widths; trace the
        # batched convs as patches+einsum (batched GEMMs) instead
        if jax.default_backend() == "cpu":
            return conv_via_patches()
        return contextlib.nullcontext()

    def bucket_clients_in_turn(self) -> bool:
        # off CPU, vmapped per-client convs lower to grouped convolutions
        # at about twice a lone step's time, and a 32-image step fills the
        # chip by itself; XLA CPU runs convs inside a loop several times
        # slower, so CPU keeps the unrolled vmapped scan
        return jax.default_backend() != "cpu"

    def samples_from_rows(self, params, rows):
        # square NHWC images, C being the stem's input channels
        c = params["stem"]["conv"].shape[2]
        hw = math.isqrt(rows.shape[1] // c)
        return rows.reshape(rows.shape[0], hw, hw, c)


register_family(CnnFamily())
