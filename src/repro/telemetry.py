"""Named spans of a DR-FL round's layers, and the names of its device
programs, for profilers and the readers of their traces.

Each layer runs inside a ``jax.profiler`` span named in :data:`SPANS`;
counts the layer already holds on the host ride on the span as stats.
Spans stamp the profiler's own clock, the one the device trace uses, so a
gap in the device's work can be put down to the layer whose span covers
it.  With the profiler off a span costs about a microsecond and records
nothing.

:data:`PROGRAMS` names the jitted functions of the round's hot path: the
trace's ``XLA Modules`` events are ``jit_<name>(<id>)``, so a rename of
one of them must update this table.
"""
from __future__ import annotations

import jax

#: span name of each layer of a round, outermost first
SPANS = {
    "round": "drfl.round",                  # one loop body of the sync engine
    "select": "drfl.select",                # selector.select
    "charge": "drfl.charge",                # cost matrix, charge, its pull
    "bucket_schedule": "drfl.bucket_schedule",  # host schedules of buckets
    "client_update": "drfl.client_update",  # one bucket program
    "aggregate": "drfl.aggregate",          # stacked layer-aligned aggregation
    "eval": "drfl.eval",                    # server evaluation
    "qmix": "drfl.qmix",                    # replay flush and QMIX updates
}

#: jitted function name of each layer's device program
PROGRAMS = {"client_update": "_bucket_program",
            "aggregate": "_stacked_agg_program"}


def span(layer: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """Host span of ``layer`` (a key of :data:`SPANS`) carrying ``counts``."""
    return jax.profiler.TraceAnnotation(SPANS[layer], **counts)


def round_span(t: int) -> jax.profiler.StepTraceAnnotation:
    """Step span of round ``t``; the round index rides as ``step_num``."""
    return jax.profiler.StepTraceAnnotation(SPANS["round"], step_num=t)
