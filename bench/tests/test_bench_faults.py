"""A whole run of the harness on the CPU (the look for a chip skipped),
with the timed path broken underneath, comes out not correct; unbroken,
it comes out correct.  One chip runs this cell, so there is no exchange
between chips to leave out."""
import contextlib

import jax
import numpy as np
import pytest

import tiny_cell
from repro.fl import batch as fl_batch
from repro.fl import engine
from repro.fl import server

SEED = 2 ** 31 + 5


@contextlib.contextmanager
def patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def state_unchanged(orig):
    """The aggregation step returns the global model as it came."""
    def agg(global_params, buckets, *args, **kwargs):
        out = orig(global_params, buckets, *args, **kwargs)
        return (global_params, out[1]) if isinstance(out, tuple) \
            else global_params
    return agg


def half_batch(orig):
    """Every client step averages over half of its batch."""
    def program(sub, x_all, y_all, gather, valid, **kwargs):
        return orig(sub, x_all, y_all, gather[..., :gather.shape[-1] // 2],
                    valid, **kwargs)
    return program


def accuracy_altered(orig):
    """One exit's accuracy is reported 0.1 off where it is computed."""
    def evaluate(*args, **kwargs):
        accs = np.array(orig(*args, **kwargs), np.float64)
        accs[0] += 0.1
        return accs
    return evaluate


def charge_altered(orig):
    """Each device is charged one joule more than its round costs."""
    def charge(fleet, need, active):
        return orig(fleet, need + 1.0, active)
    return charge


FAULTS = {
    "state_unchanged": (server, "aggregate_drfl_stacked", state_unchanged),
    "half_batch": (fl_batch, "_bucket_program", half_batch),
    "accuracy_altered": (server, "evaluate", accuracy_altered),
    "charge_altered": (engine, "fleet_charge_jit", charge_altered),
}
#: the faults each cell's compared numbers must catch
CASES = [("resnet18-sync-n100", f) for f in sorted(FAULTS)]


def _run(workload="resnet18-sync-n100"):
    cell = tiny_cell.loaded(workload)
    cell["config"]["fl"]["local_epochs"] = 1
    return tiny_cell.bench_run.run_cell(cell, SEED, 0.0, False, warmup=False)


def test_unbroken_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["setup"]) == {"cold_cache", "cache_misses",
                                 "compile_and_load_s"}
    assert set(res["metrics"]) == {"setup_s", "rounds_per_s", "peak_hbm_gib"}


def test_traced_window_profiles_the_trace_rounds(tmp_path):
    from harness.drive import TRACE_ROUNDS, Runner
    from repro.fl import FLConfig
    cell = tiny_cell.loaded()
    cell["config"]["fl"]["local_epochs"] = 1
    fl = tiny_cell.bench_run.fl_settings(cell, SEED)
    rec = Runner(FLConfig(**fl), seconds=0.0,
                 deployment_seed=cell["traffic"]["deployment_seed"],
                 trace_dir=str(tmp_path), warmup=False).run()
    assert rec.traced_rounds == list(range(1, 1 + TRACE_ROUNDS))
    assert rec.rounds == 1 + TRACE_ROUNDS
    assert rec.trace_file.endswith(".xplane.pb")


@pytest.mark.parametrize("workload,fault", CASES)
def test_broken_run_is_not_correct(workload, fault):
    module, name, make = FAULTS[fault]
    with patched(module, name, make):
        res = _run(workload)
    assert not res["correct"], res["checks"]
    jax.clear_caches()
