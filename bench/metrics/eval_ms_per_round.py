"""Host milliseconds per traced round inside server evaluation on the validation split (it returns host arrays): the harness's
``bench:eval`` span."""
from harness import rounds


def read(ctx):
    return rounds.per_round_ms(
        ctx, ctx["tr"].host_seconds(ctx["trace"], "bench:eval"))
