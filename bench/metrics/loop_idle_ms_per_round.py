"""Chip idle milliseconds per round that no ``drfl.*`` layer span of the
program covers: the round loop's own host work between layers.  Only rounds
whose ``drfl.round`` span was recorded whole count, so the window's edges
(the first and last traced rounds' unrecorded selection) stay out."""
from harness import program


def read(ctx):
    idle = program.idle_by_layer(ctx)
    n = len(program.whole_rounds(ctx)) if idle is not None else 0
    return 1000.0 * idle[program.LOOP] / n if n else None
