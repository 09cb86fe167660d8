"""The readers of the program's own layer spans (``harness/program.py`` and
the metrics built on it), on hand-made spans and device events, on a trace
recorded here, and on a trace with no program span."""
import glob
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import files, program  # noqa: E402
from harness import trace as tr  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "probe_tpu.xplane.pb")
READERS = ("eval_idle_ms_per_round", "loop_idle_ms_per_round",
           "bucket_device_ms_per_slot")


def _ctx(trace, spans, traced_rounds=(1, 2), trace_file=None):
    record = types.SimpleNamespace(trace_file=trace_file,
                                   traced_rounds=list(traced_rounds))
    ctx = {"trace": trace, "record": record, "tr": tr}
    if spans is not None:
        ctx["program_spans"] = [program.Span(*s) for s in spans]
    return ctx


def _hand_ctx():
    # window [0, 100); ops at [10, 20), [50, 60), [85, 90), so the chip
    # idles in [0, 10), [20, 50), [60, 85), [90, 100): 75 ns
    ops = [("%fusion.1 = f32[] fusion()", 10, 10),
           ("%fusion.2 = f32[] fusion()", 50, 10),
           ("%fusion.3 = f32[] fusion()", 85, 5)]
    modules = [("jit__bucket_program(1)", 10, 10),
               ("jit__bucket_program(1)", 120, 10)]
    spans = [("drfl.round", 5, 90, {"step_num": 1}),
             ("drfl.select", 0, 15, {}),
             ("drfl.client_update", 15, 30, {"slots": 8, "real_steps": 3}),
             ("drfl.eval", 40, 40, {}),
             ("drfl.qmix", 70, 5, {}),
             # the next round, still open when the window ends
             ("drfl.round", 95, 25, {"step_num": 2}),
             # past the window: neither its idle nor its slots count
             ("drfl.client_update", 120, 10, {"slots": 100})]
    return _ctx(tr.from_events((0, 100), ops, modules), spans)


def test_idle_goes_to_the_innermost_layer_span():
    idle = program.idle_by_layer(_hand_ctx())
    # select [0, 10); client_update [20, 40); eval [40, 50) (it opened
    # after client_update), [60, 70), [75, 80); qmix [70, 75) inside eval;
    # no layer span over [80, 85) and [90, 95) in the whole round; [95,
    # 100) only under the round the window cut
    want = {"select": 10, "client_update": 20, "eval": 25, "qmix": 5,
            program.LOOP: 10, program.EDGE: 5}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})


def test_loop_idle_counts_only_whole_rounds():
    # the chip idles all through [0, 100); round 1's spans opened before
    # the trace, round 3's close after it: only round 2 counts, and round
    # 1's unrecorded selection in [0, 20) is edge, not loop
    spans = [("drfl.eval", 10, 10, {}),
             ("drfl.round", 20, 60, {"step_num": 2}),
             ("drfl.select", 20, 10, {}),
             ("drfl.select", 90, 10, {})]
    ctx = _ctx(tr.from_events((0, 100), []), spans)
    want = {"eval": 10, "select": 20, program.LOOP: 50, program.EDGE: 20}
    assert program.idle_by_layer(ctx) == pytest.approx(
        {k: v * 1e-9 for k, v in want.items()})
    loop = files.metric_reader("loop_idle_ms_per_round")
    assert loop.read(ctx) == pytest.approx(50e-9 * 1e3)
    # no whole round in the window: no reading, not 0
    ctx = _ctx(tr.from_events((0, 100), []), spans[:1] + spans[2:])
    assert loop.read(ctx) is None


def test_idle_split_is_a_partition_of_the_window_idle():
    ctx = _hand_ctx()
    t = ctx["trace"]
    total = t.window_s - tr.busy_s(t)
    assert sum(program.idle_by_layer(ctx).values()) == pytest.approx(total)
    assert tr.idle_share(t) == pytest.approx(0.75)


def test_readers_on_hand_counts():
    ctx = _hand_ctx()
    got = {name: files.metric_reader(name).read(ctx) for name in READERS}
    assert got == pytest.approx({
        # eval: idle per traced round (two rounds); loop: per whole
        # recorded round (one), in ms
        "eval_idle_ms_per_round": 25e-9 * 1e3 / 2,
        "loop_idle_ms_per_round": 10e-9 * 1e3,
        # 10 ns of bucket program in the window over its 8 slots
        "bucket_device_ms_per_slot": 10e-9 * 1e3 / 8,
    })


def test_readers_give_none_without_program_spans():
    # what a program without the spans gives: the recorded v5e trace has
    # host spans of its own, none of them ``drfl.*``
    t = tr.load(RECORDED, span_prefix="bench.span.", window_span=None)
    ctx = _ctx(t, None, trace_file=RECORDED)
    assert program.spans(ctx) == []
    assert program.idle_by_layer(ctx) is None
    for name in READERS:
        assert files.metric_reader(name).read(ctx) is None
    # and a run whose trace only has layers other than eval
    ctx = _ctx(t, [("drfl.select", 0, 1, {})])
    assert files.metric_reader("eval_idle_ms_per_round").read(ctx) is None
    assert files.metric_reader("bucket_device_ms_per_slot").read(ctx) is None
    # slots but no bucket program on the device (a CPU trace)
    ctx = _ctx(tr.from_events((0, 10), [], []),
               [("drfl.client_update", 0, 5, {"slots": 4})])
    assert files.metric_reader("bucket_device_ms_per_slot").read(ctx) is None


def test_load_reads_the_spans_and_their_counts(tmp_path):
    import jax
    from repro import telemetry
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        with telemetry.round_span(4):
            with telemetry.span("client_update", real_steps=3, slots=8):
                pass
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    got = {s.name: s for s in program.load(path)}
    assert set(got) == {"drfl.round", "drfl.client_update"}
    assert got["drfl.round"].counts["step_num"] == 4
    assert got["drfl.client_update"].counts == {"real_steps": 3, "slots": 8}
    outer, inner = got["drfl.round"], got["drfl.client_update"]
    assert outer.start <= inner.start and inner.end <= outer.end
