"""Plain reference of ``resnet18-cifar10``: the multi-exit ResNet-18 in
straightforward ``jax.numpy``, written from the published description
(He et al. arXiv:1512.03385, CIFAR variant; DR-FL arXiv:2405.08183
section 5.1.1) and the layout in ``resnet18-cifar10.json``.

It imports nothing of the system under test.  It makes its own data and
weights from the seed, with the same random streams the system uses, so
that both start from the same point:

* data: class prototypes (three octaves of coarse Gaussian fields) plus
  unit Gaussian noise, drawn with numpy's default generator;
* weights: He-normal convolutions, unit-variance linear heads scaled by
  1/sqrt(fan_in), GroupNorm scale 1 and bias 0, with ``jax.random`` keys
  split in the order stem, then per stage its two blocks and its exit.

Convolutions and matmuls run at the TPU's default precision, as the
configuration states; the arithmetic's dtype is the parameters' and
inputs' (float32 for the reference, bfloat16 for the control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STAGE_CHANNELS = (64, 128, 256, 512)
BLOCKS_PER_STAGE = 2
GROUPS = 8
GN_EPS = 1e-5


def num_submodels() -> int:
    return len(STAGE_CHANNELS)


def channels(width_mult: float):
    return [max(8, int(c * width_mult)) for c in STAGE_CHANNELS]


# -- data ---------------------------------------------------------------------


def _smooth_field(rng, hw, ch, octaves=3):
    img = np.zeros((hw, hw, ch), np.float32)
    for o in range(octaves):
        k = 2 ** (o + 2)
        coarse = rng.normal(size=(k, k, ch)).astype(np.float32)
        reps = int(np.ceil(hw / k))
        up = np.kron(coarse, np.ones((reps, reps, 1), np.float32))[:hw, :hw]
        img += up / (o + 1)
    return img / octaves


def make_dataset(n, num_classes, hw, noise, seed):
    """``(x [n, hw, hw, 3] float32, y [n] int32)``."""
    rng = np.random.default_rng(seed)
    protos = np.stack([_smooth_field(rng, hw, 3) for _ in range(num_classes)])
    protos *= 2.0 / max(np.abs(protos).max(), 1e-6)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = protos[y] + noise * rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    return x.astype(np.float32), y


# -- weights ------------------------------------------------------------------


def _he(key, kh, kw, cin, cout):
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * math.sqrt(2.0 / (kh * kw * cin)))


def _gn(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def init(key, num_classes, width_mult):
    chans = channels(width_mult)
    keys = iter(jax.random.split(key, 2 + len(chans) * (BLOCKS_PER_STAGE + 1)))
    params = {"stem": {"conv": _he(next(keys), 3, 3, 3, chans[0]),
                       "gn": _gn(chans[0])},
              "stages": [], "exits": []}
    cin = chans[0]
    for si, cout in enumerate(chans):
        blocks = []
        for bi in range(BLOCKS_PER_STAGE):
            stride = 2 if (bi == 0 and si > 0) else 1
            ks = jax.random.split(next(keys), 3)
            block = {"conv1": _he(ks[0], 3, 3, cin, cout), "gn1": _gn(cout),
                     "conv2": _he(ks[1], 3, 3, cout, cout), "gn2": _gn(cout)}
            if stride != 1 or cin != cout:
                block["proj"] = _he(ks[2], 1, 1, cin, cout)
            blocks.append(block)
            cin = cout
        params["stages"].append(blocks)
        k1, k2 = jax.random.split(next(keys))
        bott = max(16, cout // 2)
        params["exits"].append({
            "bottleneck": _he(k1, 1, 1, cout, bott), "gn": _gn(bott),
            "w": jax.random.normal(k2, (bott, num_classes)) / math.sqrt(bott),
            "b": jnp.zeros((num_classes,))})
    return params


# -- forward ------------------------------------------------------------------


#: the configuration's precision for convolutions and matmuls
PRECISION = jax.lax.Precision.DEFAULT


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=PRECISION)


def _groupnorm(p, x):
    b, h, w, c = x.shape
    g = min(GROUPS, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + GN_EPS)
    return (xg.reshape(b, h, w, c) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def apply_all_exits(params, x):
    """Logits of every exit ``params`` holds, stage by stage."""
    relu = jax.nn.relu
    h = relu(_groupnorm(params["stem"]["gn"],
                        _conv(x, params["stem"]["conv"], 1)))
    outs = []
    for si, stage in enumerate(params["stages"]):
        for bi, bp in enumerate(stage):
            stride = 2 if (bi == 0 and si > 0) else 1
            y = relu(_groupnorm(bp["gn1"], _conv(h, bp["conv1"], stride)))
            y = _groupnorm(bp["gn2"], _conv(y, bp["conv2"], 1))
            sc = _conv(h, bp["proj"], stride) if "proj" in bp else h
            h = relu(y + sc)
        ep = params["exits"][si]
        e = relu(_groupnorm(ep["gn"], _conv(h, ep["bottleneck"], 1)))
        e = e.mean(axis=(1, 2))
        outs.append(jnp.dot(e, ep["w"].astype(e.dtype), precision=PRECISION)
                    + ep["b"].astype(e.dtype))
    return outs


# -- operation counts ---------------------------------------------------------


def forward_flops(model_idx, hw, width_mult, num_classes=10):
    """Matmul and convolution FLOPs (2 per multiply-add) of one sample's
    forward pass through the stem, stages 0..model_idx and every exit
    those stages carry, as a client of that depth trains it."""
    chans = channels(width_mult)
    total = 2.0 * 9 * 3 * chans[0] * hw * hw
    cin, size = chans[0], hw
    for si in range(model_idx + 1):
        cout = chans[si]
        for bi in range(BLOCKS_PER_STAGE):
            stride = 2 if (bi == 0 and si > 0) else 1
            out = size // stride
            total += 2.0 * 9 * cin * cout * out * out
            total += 2.0 * 9 * cout * cout * out * out
            if stride != 1 or cin != cout:
                total += 2.0 * cin * cout * out * out
            cin, size = cout, out
        bott = max(16, cout // 2)
        total += 2.0 * cout * bott * size * size + 2.0 * bott * num_classes
    return total


def energy_flops(model_idx, hw=32, width_mult=1.0):
    """The DR-FL energy model's per-sample cost of a Model_{idx+1} (the
    paper's Eq. 5/7 input): stem, the stages' 3x3 convolutions and the
    last exit's bottleneck, without projections or classifiers."""
    chans = channels(width_mult)
    total, size, cin = 2 * 9 * 3 * chans[0] * hw * hw, hw, chans[0]
    for si in range(model_idx + 1):
        cout = chans[si]
        size = size // (2 if si > 0 else 1)
        for _ in range(BLOCKS_PER_STAGE):
            total += 2 * 9 * cin * cout * size * size
            total += 2 * 9 * cout * cout * size * size
            cin = cout
    total += 2 * cin * max(16, cin // 2) * size * size
    return total
