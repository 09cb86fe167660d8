"""Helpers the per-layer readers (``bench/metrics/*.py``) share: the rounds the trace covers and
the calls the harness recorded in them."""


def traced(ctx):
    return ctx["record"].traced_rounds


def buckets(ctx):
    rounds = set(traced(ctx))
    return [b for b in ctx["record"].buckets if b.round in rounds]


def aggs(ctx):
    rounds = set(traced(ctx))
    return [a for a in ctx["record"].aggs if a.round in rounds]


def per_round_ms(ctx, seconds):
    n = len(traced(ctx))
    return 1000.0 * seconds / n if n else None

