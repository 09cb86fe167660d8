"""Share of the executed client-step slots (P_pad x T_pad of each bucket
program, from the arguments it was called with) that are padding: real
steps come from each client's shard size, local epochs and batch."""
from harness import rounds


def read(ctx):
    calls = rounds.buckets(ctx)
    slots = sum(b.p_pad * b.t_pad for b in calls)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(b.real_steps for b in calls) / slots)
