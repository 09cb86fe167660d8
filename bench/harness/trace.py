"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

A TPU trace holds, per chip, a ``/device:TPU:<n>`` plane whose ``XLA
Modules`` line has one event per program execution (``jit_<name>(<id>)``)
and whose ``XLA Ops`` line has one event per operation; a Pallas kernel is
an op ``%<kernel>.<n> = ... custom_call_target="tpu_custom_call"``.  The
``/host:CPU`` plane holds the harness's own spans (``bench:<layer>``, see
``drive.py``) on its ``python`` line.  All events share one clock; the
device's stamps run about a millisecond ahead of the host's dispatch
stamps, which matters only for gaps of that size.

The traced window is the host span ``bench:traced``.  Busy time is the
union of the device's op intervals inside it, averaged over the chips.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
KERNEL_OP = re.compile(r'^%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)? = .*'
                       r'custom_call_target="tpu_custom_call"')
MODULE_NAME = re.compile(r"^jit_(.*?)(?:\(\d+\))?$")
OP_NAME = re.compile(r"^%([^ ]+) = ")


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self):
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[int, List[Event]]          # chip -> op events
    modules: Dict[int, List[Event]]      # chip -> program executions
    host: List[Event]                    # the harness's spans

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) * 1e-9

    def chips(self):
        return sorted(self.ops)


def load(path, span_prefix="bench:", window_span="bench:traced") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [Event(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [Event(e.name, e.start_ns,
                                           e.duration_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(span_prefix)]
    if window_span is None:
        evs = [e for chip in ops.values() for e in chip]
        window = (min(e.start for e in evs), max(e.end for e in evs))
    else:
        spans = [e for e in host if e.name == window_span]
        if not spans:
            raise ValueError(f"no {window_span!r} span in {path}")
        window = (spans[0].start, spans[0].end)
    return Trace(window=window, ops=ops, modules=modules,
                 host=[e for e in host if e.name != window_span])


def from_events(window, ops, modules=(), host=()) -> Trace:
    """A trace of one chip from plain ``(name, start_ns, dur_ns)`` tuples."""
    def evs(xs):
        return [Event(*x) for x in xs]
    return Trace(window=tuple(window), ops={0: evs(ops)},
                 modules={0: evs(modules)}, host=evs(host))


def clip(events, lo, hi) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals) -> List[Tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(trace: Trace, chip: int) -> float:
    lo, hi = trace.window
    return sum(b - a for a, b in merge(clip(trace.ops.get(chip, []), lo, hi)))


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, averaged over the chips."""
    chips = trace.chips()
    if not chips:
        return 0.0
    return sum(busy_ns(trace, c) for c in chips) / len(chips) * 1e-9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def module_name(event_name: str) -> str:
    m = MODULE_NAME.match(event_name)
    return m.group(1) if m else event_name


def module_seconds(trace: Trace, name: str) -> float:
    """Device seconds of the executions of program ``name`` (its jitted
    function's name) inside the window, averaged over the chips."""
    lo, hi = trace.window
    chips = trace.chips() or [0]
    tot = 0.0
    for c in chips:
        evs = [e for e in trace.modules.get(c, [])
               if module_name(e.name) == name]
        tot += sum(b - a for a, b in clip(evs, lo, hi))
    return tot / len(chips) * 1e-9


def kernel_events(trace: Trace, kernel: str, chip: int = 0) -> List[Event]:
    lo, hi = trace.window
    out = []
    for e in trace.ops.get(chip, []):
        m = KERNEL_OP.match(e.name)
        if m and m.group(1) == kernel and lo <= e.start < hi:
            out.append(e)
    return out


def host_seconds(trace: Trace, span: str) -> float:
    """Host seconds inside spans named ``span`` within the window."""
    lo, hi = trace.window
    return sum(b - a for a, b in clip(
        [e for e in trace.host if e.name == span], lo, hi)) * 1e-9


def idle_gaps(trace: Trace, chip: int = 0) -> List[Tuple[float, float]]:
    lo, hi = trace.window
    gaps, t = [], lo
    for a, b in merge(clip(trace.ops.get(chip, []), lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_of(trace: Trace, a: float, b: float, default="event loop") -> str:
    """The innermost harness span that covers most of ``[a, b)``."""
    best, best_cover, best_len = default, 0.0, float("inf")
    for e in trace.host:
        cover = min(b, e.end) - max(a, e.start)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and e.dur < best_len):
            best, best_cover, best_len = e.name, cover, e.dur
    return best.split(":", 1)[-1] if best_cover >= 0.5 * (b - a) else default


def idle_by_host(trace: Trace, chip: int = 0, top=10):
    """Idle seconds of the chip, summed by what the host was doing."""
    tot: Dict[str, float] = {}
    for a, b in idle_gaps(trace, chip):
        label = label_of(trace, a, b)
        tot[label] = tot.get(label, 0.0) + (b - a) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def top_ops(trace: Trace, chip: int = 0, top=10):
    """Device seconds by program and op, the largest first."""
    lo, hi = trace.window
    mods = sorted(trace.modules.get(chip, []), key=lambda e: e.start)
    starts = [e.start for e in mods]
    import bisect
    tot: Dict[str, float] = {}
    for e in trace.ops.get(chip, []):
        if not lo <= e.start < hi:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        prog = (module_name(mods[i].name)
                if i >= 0 and e.start < mods[i].end else "?")
        m = OP_NAME.match(e.name)
        op = m.group(1) if m else e.name[:60]
        key = f"{prog}/{op}"
        tot[key] = tot.get(key, 0.0) + e.dur * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]
