"""Kernel microbenchmarks: Pallas vs the pure-jnp reference, plus the XLA
fallback attention in the model.

The kernels compile on TPU and run in interpret mode elsewhere; each row's
name carries which (``_pallas`` or ``_interp``) and its derived column the
device.  On CPU the interpret-mode numbers are NOT performance claims; the
derived column records bytes/flops so the TPU roofline expectation is
visible."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, emit
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.layer_agg import layer_agg_op, layer_agg_ref
from repro.kernels.rmsnorm import rmsnorm_op, rmsnorm_ref


def _time(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def main():
    mode = "pallas" if jax.default_backend() == "tpu" else "interp"
    dev = f"device={jax.devices()[0].device_kind}"
    key = jax.random.PRNGKey(0)
    B, S, H, D = (1, 256, 4, 64) if FAST else (4, 1024, 8, 128)
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k, v = q + 0.1, q - 0.1
    us_ref = _time(lambda a, b, c: attention_ref(
        a.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        b.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        c.transpose(0, 2, 1, 3).reshape(B * H, S, D)), q, k, v)
    flops = 4 * B * H * S * S * D
    emit("kernels/attention_ref_xla", us_ref, f"flops={flops:.3g}")
    us_pal = _time(lambda a, b, c: flash_attention(a, b, c, block_q=128,
                                                   block_k=128), q, k, v)
    emit(f"kernels/flash_attention_{mode}", us_pal, f"flops={flops:.3g};{dev}")

    N, L, Dd = (8, 8, 4096) if FAST else (32, 60, 65536)
    U = jax.random.normal(key, (N, L, Dd))
    M = (jax.random.uniform(key, (N, L)) > 0.3).astype(jnp.float32)
    w = jnp.ones((N,))
    us = _time(lambda a, b, c: layer_agg_ref(a, b, c), U, M, w)
    emit("kernels/layer_agg_ref_xla", us, f"bytes={U.size * 4:.3g}")
    us = _time(lambda a, b, c: layer_agg_op(a, b, c), U, M, w)
    emit(f"kernels/layer_agg_{mode}", us, f"bytes={U.size * 4:.3g};{dev}")

    x = jax.random.normal(key, (512, 1024), jnp.float32)
    s = jnp.ones((1024,))
    us = _time(lambda a, b: rmsnorm_ref(a, b), x, s)
    emit("kernels/rmsnorm_ref_xla", us, f"bytes={x.size * 4:.3g}")
    us = _time(lambda a, b: rmsnorm_op(a, b), x, s)
    emit(f"kernels/rmsnorm_{mode}", us, f"bytes={x.size * 4:.3g};{dev}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
