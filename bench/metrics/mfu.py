"""Whole-round model FLOP utilisation: useful FLOPs of the traced rounds
over the traced window's length and the chip's bf16 peak (the rate the
TPU's default-precision float32 matmuls run at).

Useful FLOPs: for every client trained, 3x its submodel's forward FLOPs
(forward and backward) times its real samples (steps x batch, padding
left out); plus each evaluation's forward over the validation split."""
from harness import rounds


def read(ctx):
    cfg, ref = ctx["cfg"], ctx["reference"]
    flops = 0.0
    for b in rounds.buckets(ctx):
        per = ref.forward_flops(b.model_idx, cfg.hw, cfg.width_mult,
                                cfg.num_classes)
        flops += 3.0 * per * sum(b.real_samples)
    n_val = max(64, int(cfg.n_val_fraction * cfg.n_train))
    full = ref.forward_flops(ref.num_submodels() - 1, cfg.hw, cfg.width_mult,
                             cfg.num_classes)
    evals = sum(ctx["record"].evals.get(r, 0) for r in rounds.traced(ctx))
    flops += evals * n_val * full
    if flops == 0.0:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (ctx["trace"].window_s * peak)
