"""Crash-safe fleet service (tentpole): kill-and-resume bit-for-bit
parity across engine modes, seeded fault injection accounting,
poisoned-delta quarantine, and graceful-degradation terminal markers.

The contract under test: a run that is killed after a checkpoint save
and resumed from disk must produce byte-identical history and global
params to an uninterrupted run of the same config — RNG streams, the
async event heap, MARL learner state and replay included.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointHalt
from repro.checkpoint.engine import EngineCheckpointer
from repro.core.selection import GreedySelector
from repro.fl import (FaultEvent, FaultPlan, FLConfig, RoundEngine,
                      run_simulation)
from repro.fl import server as fl_server
from repro.fl.spec import ResilienceSpec, SimulationSpec
from repro.models import cnn

SMALL = dict(n_devices=8, n_rounds=6, participation=0.5, local_epochs=1,
             batch_size=8, n_train=256, hw=8, seed=3)
# faults must land on live, in-flight devices to exercise anything: give
# the fleet healthy batteries and full participation
CHURN = dict(SMALL, participation=1.0, energy_scale=50.0, n_rounds=8,
             engine_mode="async", async_time_horizon=400.0,
             fault_crashes=1, fault_timeouts=2, fault_disconnects=1,
             fault_corrupts=3)


def _canon(x):
    if isinstance(x, (np.ndarray, jax.Array)):
        a = np.asarray(x)
        return ("arr", str(a.dtype), a.tobytes())
    if isinstance(x, dict):
        return {k: _canon(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_canon(v) for v in x]
    return x


def _assert_bit_identical(ref, res):
    assert set(ref) == set(res)
    for k in ref:
        if k == "params":
            continue
        assert _canon(ref[k]) == _canon(res[k]), f"hist[{k!r}] diverged"
    ra = jax.tree.leaves(ref["params"])
    rb = jax.tree.leaves(res["params"])
    assert len(ra) == len(rb)
    for a, b in zip(ra, rb):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _kill_and_resume(cfg, tmp_path, halt_after=1, every=2):
    """Reference run, then a checkpointed run killed after ``halt_after``
    saves, then a resumed run; assert resumed == reference bit-for-bit."""
    ref = run_simulation(cfg)
    ck = dataclasses.replace(cfg, checkpoint_dir=str(tmp_path / "ck"),
                             checkpoint_every=every)
    with pytest.raises(CheckpointHalt):
        run_simulation(ck, halt_after_saves=halt_after)
    res = run_simulation(dataclasses.replace(ck, resume=True))
    _assert_bit_identical(ref, res)
    return ref


# ----------------------------------------------------------------------
# kill-and-resume parity
# ----------------------------------------------------------------------

def test_sync_marl_kill_resume_parity(tmp_path):
    # halt_after=4 lands the kill inside episode 1, so the resume has to
    # restore mid-episode MARL state (learner, replay, RNG streams) too
    cfg = FLConfig(**SMALL, marl_episodes=2)
    _kill_and_resume(cfg, tmp_path, halt_after=4)


def test_async_greedy_kill_resume_parity(tmp_path):
    cfg = FLConfig(**SMALL, engine_mode="async", selector="greedy",
                   client_executor="perclient")
    _kill_and_resume(cfg, tmp_path)


def test_async_faulted_marl_kill_resume_parity(tmp_path):
    # the acceptance case: checkpoint + kill + resume with the fault
    # timeline (reaps, rejoins, armed corruptions) mid-flight
    cfg = FLConfig(**CHURN)
    ref = _kill_and_resume(cfg, tmp_path, halt_after=2)
    assert ref["faults"]["events"], "churn config must actually fault"


@pytest.mark.slow
def test_async_set_mixer_batched_kill_resume_parity(tmp_path):
    cfg = FLConfig(**SMALL, engine_mode="async", client_executor="batched",
                   mixer_mode="set", marl_agent_budget=4, marl_episodes=2)
    _kill_and_resume(cfg, tmp_path, halt_after=3)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_resume_from_checkpoint_with_legacy_history_keys(tmp_path,
                                                         monkeypatch, mode):
    # checkpoints written before the straggler wait was renamed from
    # ``idle`` and ``wall_clock`` was dropped resume under today's keys
    load = EngineCheckpointer.load

    def legacy_load(self, path=None):
        state, meta = load(self, path)
        hist = state["hist"]
        hist["idle"] = hist.pop("straggler_wait_s")
        hist["wall_clock"] = [0.5] * len(hist["idle"])
        if mode == "async":
            state["state"]["window_wall0"] = 0.5
        return state, meta

    monkeypatch.setattr(EngineCheckpointer, "load", legacy_load)
    cfg = FLConfig(**SMALL, engine_mode=mode, selector="greedy",
                   client_executor="perclient")
    ref = _kill_and_resume(cfg, tmp_path)
    assert "idle" not in ref and "wall_clock" not in ref


def test_resume_rejects_config_drift(tmp_path):
    cfg = FLConfig(**SMALL, selector="greedy",
                   checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2)
    with pytest.raises(CheckpointHalt):
        run_simulation(cfg, halt_after_saves=1)
    drifted = dataclasses.replace(cfg, resume=True, seed=cfg.seed + 1)
    with pytest.raises(ValueError, match="refusing to resume"):
        run_simulation(drifted)


# ----------------------------------------------------------------------
# fault injection: plan + accounting
# ----------------------------------------------------------------------

def test_fault_plan_is_seed_deterministic():
    a = FaultPlan.sample(16, 100.0, crashes=2, timeouts=2, corrupts=2, seed=7)
    b = FaultPlan.sample(16, 100.0, crashes=2, timeouts=2, corrupts=2, seed=7)
    c = FaultPlan.sample(16, 100.0, crashes=2, timeouts=2, corrupts=2, seed=8)
    assert a.events == b.events and a.events != c.events
    assert all(0.0 < e.time < 100.0 for e in a.events)


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan(events=(FaultEvent(time=1.0, kind="gremlin", device=0),))
    with pytest.raises(ValueError, match="corrupt payload"):
        FaultPlan(events=(FaultEvent(time=1.0, kind="corrupt", device=0,
                                     payload="zero"),))
    with pytest.raises(ValueError, match="horizon"):
        FaultPlan.sample(4, 0.0, crashes=1)
    assert FaultPlan.from_config(FLConfig()) is None


def test_faults_require_async_engine():
    cfg = FLConfig(**SMALL, selector="greedy", fault_crashes=1,
                   fault_horizon=100.0)
    with pytest.raises(ValueError, match="async"):
        RoundEngine(cfg, GreedySelector())


def test_fault_accounting_is_complete():
    """Every planned event must surface in hist["faults"] with an
    outcome, and every poisoned delta must be quarantined — the global
    params stay finite no matter what the churn injects."""
    cfg = FLConfig(**CHURN, selector="greedy")
    plan = FaultPlan.from_config(cfg)
    hist = run_simulation(cfg)
    faults = hist["faults"]
    injected = [e for e in faults["events"] if e["injected"]]
    assert len(injected) == len(plan)
    assert all("outcome" in e for e in faults["events"])
    want = sorted((e.time, e.kind, e.device) for e in plan.events)
    got = sorted((e["time"], e["kind"], e["device"]) for e in injected)
    assert got == want
    n_poisoned = sum(1 for e in faults["events"]
                     if e.get("outcome") == "poisoned")
    assert faults["n_quarantined"] == n_poisoned == len(faults["quarantined"])
    assert n_poisoned > 0, "churn config must exercise the quarantine path"
    assert faults["n_reaped"] == sum(hist["lost"])
    assert faults["n_reaped"] > 0, "churn config must exercise reaping"
    for leaf in jax.tree.leaves(hist["params"]):
        assert np.isfinite(np.asarray(leaf)).all()
    assert hist["terminated"]["lost"] == faults["n_reaped"]


def test_all_in_flight_dead_terminates_with_marker():
    """Regression: crashing the whole fleet mid-first-wave used to leave
    completions that never arrive; now reaps reclaim the window and the
    run ends with an explicit ``fleet_dead`` terminal marker."""
    cfg = FLConfig(**dict(SMALL, participation=1.0), energy_scale=50.0,
                   engine_mode="async", selector="greedy",
                   async_time_horizon=400.0)
    plan = FaultPlan(events=tuple(
        FaultEvent(time=1.0 + 0.01 * i, kind="crash", device=i)
        for i in range(cfg.n_devices)))
    hist = RoundEngine(cfg, GreedySelector(), fault_plan=plan).run()
    assert hist["terminated"]["reason"] == "fleet_dead"
    mid = sum(1 for e in hist["faults"]["events"]
              if e["outcome"] == "crash_mid_task")
    assert mid > 0 and hist["faults"]["n_reaped"] == mid
    assert not hist["alive"] or hist["alive"][-1] == 0


# ----------------------------------------------------------------------
# quarantine at the aggregation layer
# ----------------------------------------------------------------------

def _params():
    return cnn.init(jax.random.PRNGKey(0), num_classes=10, width_mult=0.25)


@pytest.mark.parametrize("poison", [float("nan"), float("inf"), 1e30])
def test_sliced_aggregation_quarantines_bad_delta(poison):
    p = _params()
    good = jax.tree.map(lambda a: jnp.full_like(a, 1e-3), p)
    bad = jax.tree.map(lambda a: jnp.full_like(a, poison), p)
    out, valid = fl_server.aggregate_sliced(p, [good, bad], [1.0, 1.0],
                                            with_stats=True)
    valid = np.asarray(valid)
    assert valid.tolist() == [True, False]
    ref = fl_server.aggregate_sliced(p, [good], [1.0])
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_drfl_aggregation_quarantines_bad_delta():
    p = _params()
    good = jax.tree.map(lambda a: jnp.full_like(a, 1e-3), p)
    bad = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), p)
    out, valid = fl_server.aggregate_drfl(p, [good, bad], [0, 0], [1.0, 1.0],
                                          with_stats=True)
    assert np.asarray(valid).tolist() == [True, False]
    ref = fl_server.aggregate_drfl(p, [good], [0], [1.0])
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    for leaf in jax.tree.leaves(out):
        assert np.isfinite(np.asarray(leaf)).all()


def test_zero_survivor_round_leaves_params_unchanged():
    p = _params()
    bad = jax.tree.map(lambda a: jnp.full_like(a, jnp.nan), p)
    out, valid = fl_server.aggregate_sliced(p, [bad, bad], [1.0, 1.0],
                                            with_stats=True)
    assert not np.asarray(valid).any()
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(p)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# spec surface
# ----------------------------------------------------------------------

def test_resilience_spec_round_trips_through_flat():
    cfg = FLConfig(**SMALL, selector="greedy", engine_mode="async",
                   async_time_horizon=200.0, fault_crashes=2,
                   fault_horizon=100.0, fault_seed=9,
                   checkpoint_dir="/tmp/ck", checkpoint_every=4,
                   checkpoint_keep=5, task_deadline_factor=3.0)
    spec = SimulationSpec.from_flat(cfg)
    assert spec.resilience.fault_crashes == 2
    assert spec.resilience.n_faults() == 2
    flat = spec.to_flat()
    for f in ("fault_crashes", "fault_horizon", "fault_seed",
              "checkpoint_dir", "checkpoint_every", "checkpoint_keep",
              "task_deadline_factor"):
        assert getattr(flat, f) == getattr(cfg, f)


def test_resilience_spec_validation():
    with pytest.raises(ValueError, match="task_deadline_factor"):
        ResilienceSpec(task_deadline_factor=1.0)
    with pytest.raises(ValueError, match="resume"):
        ResilienceSpec(resume=True)
    with pytest.raises(ValueError, match="async"):
        SimulationSpec.from_flat(FLConfig(fault_crashes=1,
                                          fault_horizon=50.0))
