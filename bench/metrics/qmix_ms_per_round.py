"""Host milliseconds per traced round inside the engine's QMIX training call (episode flush, replay sampling, updates): the harness's
``bench:qmix`` span."""
from harness import rounds


def read(ctx):
    return rounds.per_round_ms(
        ctx, ctx["tr"].host_seconds(ctx["trace"], "bench:qmix"))
