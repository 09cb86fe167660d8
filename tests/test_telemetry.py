"""The round's layer spans (repro.telemetry) as a profiler records them:
every layer's span with its counts, bucket counts that agree with the
executor's ``COUNTERS`` and with the buckets it ran, and program names
that match the jitted functions."""
import glob

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.fl import FLConfig, run_simulation
from repro.fl import batch as fl_batch
from repro.fl import server as fl_server

#: the counts each span carries (a layer not listed carries none)
COUNTS = {
    "round": {"step_num"},
    "client_update": {"real_steps", "slots"},
}


def _host_spans(trace_dir):
    """``(name, stats)`` of every ``drfl.*`` host event in the trace."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, dict(e.stats)) for e in line.events
                    if e.name.startswith("drfl.")]
    return out


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory, monkeypatch_module):
    # one round of the MLP family keeps the bucket programs' CPU compile
    # short; marl_train_every=1 gives that round its QMIX update
    cfg = FLConfig(n_devices=8, n_rounds=1, participation=0.5, n_train=256,
                   hw=8, width_mult=0.06, batch_size=16, local_epochs=1,
                   model_family="mlp", method="drfl", selector="marl",
                   marl_train_every=1, seed=3, client_executor="batched")
    buckets = []
    orig = fl_batch.bucket_cohort

    def recording(*args, **kwargs):
        out = orig(*args, **kwargs)
        buckets.extend(out)
        return out

    monkeypatch_module.setattr(fl_batch, "bucket_cohort", recording)
    trace_dir = tmp_path_factory.mktemp("trace")
    fl_batch.reset_counters()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # the spans, not every Python call
    with jax.profiler.trace(str(trace_dir), profiler_options=options):
        hist = run_simulation(cfg)
    counters = dict(fl_batch.COUNTERS)
    return hist, buckets, counters, _host_spans(trace_dir)


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as mp:
        yield mp


def test_every_layer_span_is_recorded_with_its_counts(traced_run):
    hist, _, _, spans = traced_run
    assert len(hist["acc_mean"]) == 1
    names = {name for name, _ in spans}
    assert names == set(telemetry.SPANS.values())
    for layer, name in telemetry.SPANS.items():
        for got, stats in spans:
            if got == name:
                # the profiler marks a step span with ``_r``
                assert (set(stats) - {"_r"} == COUNTS.get(layer, set())), (
                    layer, stats)
    rounds = [s["step_num"] for n, s in spans if n == "drfl.round"]
    assert rounds == [0]


def test_bucket_counts_agree_with_counters_and_buckets(traced_run):
    _, buckets, counters, spans = traced_run
    calls = [s for n, s in spans if n == "drfl.client_update"]
    assert buckets and len(calls) == len(buckets) == counters["executions"]
    slots = sum(b.gather.shape[0] * b.gather.shape[1] for b in buckets)
    real = sum(int(np.count_nonzero(b.valid)) for b in buckets)
    assert sum(s["slots"] for s in calls) == counters["slots"] == slots
    assert (sum(s["real_steps"] for s in calls) == counters["real_steps"]
            == real)
    assert 0 < real < slots          # pow2 padding: some slots are masked
    # one span per bucket program, in the order the buckets ran
    assert [(s["slots"], s["real_steps"]) for s in calls] == [
        (b.gather.shape[0] * b.gather.shape[1],
         int(np.count_nonzero(b.valid))) for b in buckets]


def test_reset_clears_every_counter():
    fl_batch.COUNTERS["slots"] += 3
    fl_batch.reset_counters()
    assert set(fl_batch.COUNTERS.values()) == {0}


def test_program_table_names_the_jitted_functions():
    assert telemetry.PROGRAMS == {
        "client_update": fl_batch._bucket_program.__name__,
        "aggregate": fl_server._stacked_agg_program.__name__,
    }
