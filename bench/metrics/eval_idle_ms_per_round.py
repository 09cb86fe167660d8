"""Chip idle milliseconds per traced round inside the program's own
``drfl.eval`` spans (server evaluation, fed batch by batch from the host):
the part of eval's host time that leaves the chip waiting."""
from harness import program, rounds


def read(ctx):
    idle = program.idle_by_layer(ctx)
    if idle is None or "eval" not in idle:
        return None
    return rounds.per_round_ms(ctx, idle["eval"])
