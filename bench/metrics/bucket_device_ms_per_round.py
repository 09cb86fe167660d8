"""Device milliseconds per traced round of the client-update bucket
programs (``fl/batch._bucket_program``, found by its jitted name)."""
from harness import rounds


def read(ctx):
    s = ctx["tr"].module_seconds(ctx["trace"], "_bucket_program")
    return rounds.per_round_ms(ctx, s) if s > 0 else None
