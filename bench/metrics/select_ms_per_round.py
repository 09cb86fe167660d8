"""Host milliseconds per traced round inside the selector's `select` (MARL agents' forward, affordability mask, top-k; it ends in its own device_get): the harness's
``bench:select`` span."""
from harness import rounds


def read(ctx):
    return rounds.per_round_ms(
        ctx, ctx["tr"].host_seconds(ctx["trace"], "bench:select"))
