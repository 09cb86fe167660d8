"""The program's own layer spans in a traced run, for the per-layer
readers (``bench/metrics/*.py``).

The system under test runs each layer of a round inside a ``drfl.*`` host
span (``repro.telemetry``), on the clock the device trace uses, with the
counts the layer holds as the span's stats.  This module reads them from
the run's ``.xplane.pb``, keeps those that overlap the traced window, and
splits the chip's idle time among them.  A program that records no such
span gives an empty list here, and every reader built on it gives None.

The profiler records a span only if it opened after the trace started and
closed before it stopped.  The traced window starts and stops inside a
round's selection, so the first traced round's ``drfl.round`` and
``drfl.select`` spans, and the last one's, are missing: idle time under no
recorded span is put down to the window's :data:`EDGE`, not to the loop.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

PREFIX = "drfl."
#: idle time inside a whole recorded round span that no layer span covers:
#: the round loop's own host work
LOOP = "loop"
#: idle time under no recorded span: the window's edges, where the round's
#: spans opened before the trace started or closed after it stopped
EDGE = "edge"


@dataclasses.dataclass
class Span:
    name: str
    start: float                     # ns
    dur: float                       # ns
    counts: Dict[str, int]

    @property
    def end(self):
        return self.start + self.dur


def load(path) -> List[Span]:
    """Every ``drfl.*`` event of the trace's host planes."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [Span(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX)]
    return out


def spans(ctx) -> List[Span]:
    """The program spans that overlap the traced window (the file is read
    once and kept in ``ctx``)."""
    if "program_spans" not in ctx:
        path = ctx["record"].trace_file
        ctx["program_spans"] = load(path) if path else []
    lo, hi = ctx["trace"].window
    return [s for s in ctx["program_spans"] if s.start < hi and s.end > lo]


def _telemetry():
    # imported only once spans were found: a program without them may not
    # have the module either
    from repro import telemetry
    return telemetry


def of_layer(ctx, layer) -> List[Span]:
    """The window's spans of ``layer`` (a key of ``telemetry.SPANS``)."""
    found = spans(ctx)
    if not found:
        return []
    name = _telemetry().SPANS[layer]
    return [s for s in found if s.name == name]


def whole_rounds(ctx) -> List[Span]:
    """The window's ``drfl.round`` spans that lie wholly inside it."""
    lo, hi = ctx["trace"].window
    return [s for s in of_layer(ctx, "round")
            if lo <= s.start and s.end <= hi]


def program_name(layer) -> str:
    """Jitted function name of ``layer``'s device program (ask only once
    spans were found)."""
    return _telemetry().PROGRAMS[layer]


def _split(a, b, layers, rounds, out):
    """Adds the idle interval ``[a, b)`` to ``out`` piece by piece, each
    under the innermost layer span over it (the one that opened last),
    else under :data:`LOOP` inside a whole round span, else under
    :data:`EDGE`."""
    cover = [s for s in layers + rounds if s.start < b and s.end > a]
    cuts = sorted({a, b, *(t for s in cover for t in (s.start, s.end)
                          if a < t < b)})
    for p, q in zip(cuts, cuts[1:]):
        over = [s for s in cover if s.start <= p and s.end >= q]
        inner = [s for s in over if s in layers]
        name = (max(inner, key=lambda s: (s.start, -s.dur)).name if inner
                else LOOP if over else EDGE)
        out[name] += q - p


def idle_by_layer(ctx) -> Optional[Dict[str, float]]:
    """Seconds the chip sat idle in the traced window, by layer (the keys
    of ``telemetry.SPANS``), :data:`LOOP` and :data:`EDGE`, averaged over
    the chips: a partition of the window's idle time.  Every layer with a
    span in the window has a key.  None where the trace has no program
    span."""
    found = spans(ctx)
    if not found:
        return None
    layer_of = {name: layer for layer, name in _telemetry().SPANS.items()}
    layers = [s for s in found if layer_of.get(s.name) != "round"]
    rounds = whole_rounds(ctx)
    out = dict.fromkeys([s.name for s in layers] + [LOOP, EDGE], 0.0)
    tr, trace = ctx["tr"], ctx["trace"]
    chips = trace.chips() or [0]
    for chip in chips:
        for a, b in tr.idle_gaps(trace, chip):
            _split(a, b, layers, rounds, out)
    return {layer_of.get(k, k): v / len(chips) * 1e-9
            for k, v in out.items()}
