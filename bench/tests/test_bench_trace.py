"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (three rounds of a ``layer_agg`` call, a named jitted matmul
and an ``rmsnorm`` call, with host spans around the first two)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import trace as tr  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "probe_tpu.xplane.pb")
KERNEL = '%layer_agg.1 = f32[8,128] custom-call(), custom_call_target="tpu_custom_call"'


def _hand_trace():
    # window [0, 100); ops overlap in [10, 30) and [25, 40); a kernel at
    # [60, 70); one op sticks out past the window's end
    ops = [("%fusion.1 = f32[] fusion()", 10, 20), ("%fusion.2 = f32[] fusion()", 25, 15),
           (KERNEL, 60, 10), ("%copy.3 = f32[] copy()", 95, 20)]
    modules = [("jit__bucket_program(7)", 5, 40), ("jit_layer_agg_op(3)", 55, 20)]
    host = [("bench:bucket_program", 0, 45), ("bench:select", 40, 20)]
    return tr.from_events((0, 100), ops, modules, host)


def test_busy_union_merges_overlaps_and_clips_to_window():
    t = _hand_trace()
    # [10, 40) + [60, 70) + [95, 100) = 30 + 10 + 5
    assert tr.busy_ns(t, 0) == pytest.approx(45)
    assert tr.idle_share(t) == pytest.approx(1 - 45 / 100)


def test_kernel_and_module_time_by_name():
    t = _hand_trace()
    assert [e.dur for e in tr.kernel_events(t, "layer_agg")] == [10]
    assert tr.kernel_events(t, "rmsnorm") == []
    assert tr.module_seconds(t, "_bucket_program") == pytest.approx(40e-9)
    assert tr.module_seconds(t, "layer_agg_op") == pytest.approx(20e-9)


def test_idle_gaps_are_labelled_by_the_host_span():
    t = _hand_trace()
    assert tr.idle_gaps(t) == [(0, 10), (40, 60), (70, 95)]
    by = dict(tr.idle_by_host(t))
    assert by["bucket_program"] == pytest.approx(10e-9)
    assert by["select"] == pytest.approx(20e-9)
    assert by["event loop"] == pytest.approx(25e-9)


def test_recorded_tpu_trace():
    t = tr.load(RECORDED, span_prefix="bench.span.", window_span=None)
    agg = tr.kernel_events(t, "layer_agg")
    assert len(agg) == 3 and len(tr.kernel_events(t, "rmsnorm")) == 3
    # a [16, 1024, 1024] f32 stack is 64 MiB: about 97 us at ~700 GB/s
    assert all(90e3 < e.dur < 110e3 for e in agg)
    busy = tr.busy_s(t)
    assert 0 < busy < t.window_s
    assert tr.module_seconds(t, "layer_agg_op") >= sum(e.dur for e in agg) * 1e-9
    assert tr.module_seconds(t, "_named_prog") > 0
    top = dict(tr.top_ops(t))
    assert top["layer_agg_op/layer_agg.1"] == pytest.approx(
        sum(e.dur for e in agg) * 1e-9)
