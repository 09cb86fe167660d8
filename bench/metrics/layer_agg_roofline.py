"""Roofline share of the ``layer_agg`` Pallas kernel: the least time the
chip needs for the traced calls' algorithm FLOPs and bytes (on unpadded
shapes: real clients N, the rows R the model's groups fill, segment
width D) over the kernel's device time in the trace."""
import math

import jax

from harness import rounds

SEG = 1024


def model_rows(ctx):
    cfg, ref = ctx["cfg"], ctx["reference"]
    shapes = jax.eval_shape(
        lambda k: ref.init(k, cfg.num_classes, cfg.width_mult),
        jax.random.PRNGKey(0))
    groups = ([shapes["stem"]] + list(shapes["stages"])
              + list(shapes["exits"]))
    return sum(max(1, math.ceil(sum(l.size for l in jax.tree.leaves(g)) / SEG))
               for g in groups)


def read(ctx):
    events = ctx["tr"].kernel_events(ctx["trace"], "layer_agg")
    calls = rounds.aggs(ctx)
    if not events or not calls:
        return None
    cost = ctx["kernel_cost"]("layer_agg").cost
    R = model_rows(ctx)
    flops = nbytes = 0.0
    for a in calls:
        f, b = cost(N=a.n_real, R=R, D=SEG)
        flops, nbytes = flops + f, nbytes + b
    peaks = ctx["peaks"]
    need = max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * need / (sum(e.dur for e in events) * 1e-9)
