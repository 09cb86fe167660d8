"""Device milliseconds per traced round of the stacked aggregation
program (``fl/server._stacked_agg_program``, found by its jitted name)."""
from harness import rounds


def read(ctx):
    s = ctx["tr"].module_seconds(ctx["trace"], "_stacked_agg_program")
    return rounds.per_round_ms(ctx, s) if s > 0 else None
