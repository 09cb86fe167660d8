"""Event-driven round engine (repro.fl.engine) vs the frozen reference loop.

Contracts:
* ``engine_mode="sync"`` reproduces the legacy synchronous round loop
  (``simulation._run_once_reference``) BIT-FOR-BIT — acc per exit, energy
  ledger, round times, participant sets, rewards — for both the greedy and
  the MARL selector, with and without hot-plug.
* ``engine_mode="async"`` does the same amount of client work without a
  round barrier: staleness-aware per-event aggregation, strictly lower
  straggler wait, hot-plug as a timeline event (full batteries, current
  global model, Top-K repriced at the join).
* staleness-aware ``aggregate_drfl`` damps stale deltas by (1+s)^-decay
  and leaves fresh (s=0) aggregation bit-for-bit unchanged.
* client-update seeds are collision-free across (round, device) — the old
  ``base*1000 + t*100 + i`` mix collided for any 100+ device fleet.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl import FLConfig, run_simulation
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.fl.simulation import _run_once_reference
from repro.models import cnn

PARITY_KEYS = ("acc_mean", "energy", "round_time", "alive", "participants",
               "model_choices", "reward", "dropouts")


def _assert_parity(h_engine, h_ref):
    for key in PARITY_KEYS:
        assert h_engine[key] == h_ref[key], key
    for a, b in zip(h_engine["acc"], h_ref["acc"]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(h_engine["final_acc"], h_ref["final_acc"])


# ---------------------------------------------------------------------------
# sync mode: bit-for-bit parity with the frozen reference loop
# ---------------------------------------------------------------------------


def test_sync_parity_greedy_with_hotplug():
    cfg = FLConfig(n_devices=5, n_rounds=4, participation=0.6, n_train=600,
                   local_epochs=1, method="drfl", selector="greedy", seed=4,
                   hotplug_round=2, hotplug_n=3)
    h_engine = run_simulation(cfg)
    h_ref, _, _ = _run_once_reference(cfg)
    _assert_parity(h_engine, h_ref)


def test_sync_parity_marl():
    cfg = FLConfig(n_devices=6, n_rounds=4, participation=0.5, n_train=500,
                   local_epochs=1, method="drfl", selector="marl", seed=0)
    h_engine = run_simulation(cfg)
    h_ref, _, _ = _run_once_reference(cfg)
    _assert_parity(h_engine, h_ref)


def test_sync_parity_baseline_method():
    cfg = FLConfig(n_devices=6, n_rounds=2, participation=0.5, n_train=500,
                   local_epochs=1, method="heterofl", seed=1)
    h_engine = run_simulation(cfg)
    h_ref, _, _ = _run_once_reference(cfg)
    _assert_parity(h_engine, h_ref)


def test_sync_reports_straggler_wait():
    cfg = FLConfig(n_devices=6, n_rounds=3, participation=0.5, n_train=500,
                   local_epochs=1, method="drfl", selector="greedy", seed=1)
    h = run_simulation(cfg)
    # heterogeneous tiers: some participant always outpaces the straggler
    assert h["engine"] == "sync"
    assert h["idle_time"] > 0.0
    assert len(h["straggler_wait_s"]) == len(h["round_time"])
    assert h["sim_time_total"] == pytest.approx(sum(h["round_time"]))


# ---------------------------------------------------------------------------
# async mode: event timeline
# ---------------------------------------------------------------------------


def _async_cfg(**kw):
    base = dict(n_devices=8, n_rounds=4, participation=0.5, n_train=600,
                local_epochs=1, method="drfl", selector="greedy", seed=1,
                engine_mode="async")
    base.update(kw)
    return FLConfig(**base)


def test_async_same_work_lower_straggler_wait():
    cfg = _async_cfg()
    h_sync = run_simulation(dataclasses.replace(cfg, engine_mode="sync"))
    h_async = run_simulation(cfg)
    # same client-task budget as the sync run dispatched at most...
    assert h_async["n_tasks"] <= cfg.n_rounds * 4
    assert h_async["n_tasks"] == sum(len(p) for p in h_async["participants"])
    # ... finished in no more simulated time, with strictly less idle
    assert h_async["sim_time_total"] <= h_sync["sim_time_total"] + 1e-6
    assert h_sync["idle_time"] > 0.0
    assert h_async["idle_time"] < h_sync["idle_time"]
    assert np.isfinite(h_async["acc_mean"]).all()
    # per-event aggregation: one version bump per arriving update
    assert h_async["n_aggregations"] == len(h_async["staleness"])


def test_async_staleness_observed_and_bounded():
    h = run_simulation(_async_cfg())
    stale = np.asarray(h["staleness"])
    assert (stale >= 0).all()
    # overlapping tasks mean SOME update lands late
    assert stale.max() >= 1
    assert stale.max() < h["n_aggregations"]


def test_async_respects_time_horizon():
    cfg = _async_cfg()
    h_full = run_simulation(cfg)
    horizon = h_full["sim_time_total"] * 0.5
    h_cut = run_simulation(dataclasses.replace(
        cfg, async_time_horizon=horizon))
    assert h_cut["sim_time_total"] <= horizon + 1e-6
    assert h_cut["n_tasks"] < h_full["n_tasks"]


def test_async_marl_arm_runs():
    cfg = _async_cfg(selector="marl", n_devices=6, participation=0.5, seed=0)
    h = run_simulation(cfg)
    assert h["n_tasks"] > 0
    assert np.isfinite(h["reward"]).all()


def test_async_marl_custom_task_budget():
    # a budget larger than the sync equivalent must size the replay buffer
    # from the ACTUAL budget (regression: episode overflow at add_episode)
    cfg = _async_cfg(selector="marl", n_devices=6, participation=0.5, seed=0,
                     async_task_budget=30)
    h = run_simulation(cfg)
    assert 0 < h["n_tasks"] <= 30


def test_async_energy_ledger_monotone():
    h = run_simulation(_async_cfg())
    e = h["energy"]
    assert all(e[i + 1] <= e[i] + 1e-6 for i in range(len(e) - 1))
    assert e[-1] >= 0.0


# ---------------------------------------------------------------------------
# hot-plug as a timeline event (ISSUE 2 satellite)
# ---------------------------------------------------------------------------


def test_async_hotplug_joins_on_timeline_event():
    cfg = _async_cfg(n_devices=5, participation=1.0, n_rounds=6, seed=4,
                     hotplug_round=2, hotplug_n=3, energy_scale=0.5)
    h = run_simulation(cfg)
    hp = h["hotplug"]
    assert hp is not None
    # joins with FULL (scaled) batteries at the join event
    from repro.core.energy import BATTERY_JOULES
    assert len(hp["join_remaining"]) == 3
    for r in hp["join_remaining"]:
        assert r == pytest.approx(BATTERY_JOULES * 0.5, rel=0.25)
    # Top-K k is repriced on the join event itself: 5 -> 8 connected
    assert hp["k_before"] == 5
    assert hp["k_after"] == 8
    assert h["k_final"] == 8
    # joined devices are dispatched, and every task they run was sent with
    # the CURRENT global model (a snapshot no older than the join version)
    join_tasks = [t for t in h["task_log"] if t["device"] >= 5]
    assert join_tasks, "hot-plug devices never participated"
    assert all(t["version"] >= hp["version"] for t in join_tasks)
    assert all(t["t_dispatch"] >= hp["sim_time"] - 1e-9 for t in join_tasks)
    # the join event itself opens dispatch slots: with full batteries and
    # greedy energy-ordered Top-K, a joiner is dispatched AT the join time
    assert any(t["t_dispatch"] == pytest.approx(hp["sim_time"])
               for t in join_tasks)


def test_async_hotplug_joins_even_when_initial_fleet_stalls():
    """If the initial fleet drains before the join boundary, the event heap
    empties with no completion left to advance the virtual round — but sync
    mode reaches the join by ticking empty rounds, so async must force the
    hot-plug rather than strand fresh-battery joiners offline."""
    cfg = _async_cfg(n_devices=4, participation=1.0, n_rounds=6, seed=0,
                     hotplug_round=4, hotplug_n=3, energy_scale=0.001)
    h = run_simulation(cfg)
    hp = h["hotplug"]
    assert hp is not None
    # the join fired before the boundary round count was ever reached
    assert hp["vround"] < 4
    # and the joiners actually took work
    assert any(t["device"] >= 4 for t in h["task_log"])


# ---------------------------------------------------------------------------
# FLEnv event-time mode (repro.fl.environment)
# ---------------------------------------------------------------------------


def test_fl_env_async_event_time():
    from repro.fl.environment import FLEnv, FLEnvConfig
    env = FLEnv(FLEnvConfig(n_devices=6, n_rounds=4, seed=0, mode="async"))
    env.reset()
    _, r0, _, i0 = env.step(np.full(6, 0))
    # everyone got dispatched; the clock advanced to the FIRST completion,
    # not the barrier, and there is no straggler wait
    assert 0.0 < i0["sim_time"] < i0["round_time"]
    assert i0["idle_time"] == 0.0
    # mid-task devices auto-abstain: re-issuing actions spends energy only
    # for devices whose virtual clock has freed up
    e_before = i0["energy"]
    _, _, _, i1 = env.step(np.full(6, 0))
    busy_spend = e_before - i1["energy"]
    env_sync = FLEnv(FLEnvConfig(n_devices=6, n_rounds=4, seed=0,
                                 mode="sync"))
    env_sync.reset()
    _, _, _, s0 = env_sync.step(np.full(6, 0))
    _, _, _, s1 = env_sync.step(np.full(6, 0))
    assert busy_spend < (s0["energy"] - s1["energy"])
    assert s0["idle_time"] > 0.0
    assert s0["sim_time"] == pytest.approx(s0["round_time"])


# ---------------------------------------------------------------------------
# staleness-aware aggregation (repro.fl.server)
# ---------------------------------------------------------------------------


def _tiny_params_and_delta():
    params = cnn.init(jax.random.PRNGKey(0), 10, width_mult=0.25)
    delta = jax.tree.map(jnp.ones_like, params)
    return params, delta


def test_staleness_scale_values():
    assert fl_server.staleness_scale(0, 0.5) == 1.0
    assert fl_server.staleness_scale(3, 0.5) == pytest.approx(0.5)
    assert fl_server.staleness_scale(1, 1.0) == pytest.approx(0.5)
    s = [fl_server.staleness_scale(i, 0.5) for i in range(5)]
    assert s == sorted(s, reverse=True)      # monotone damping


def test_aggregate_drfl_fresh_staleness_bitexact():
    params, delta = _tiny_params_and_delta()
    ref = fl_server.aggregate_drfl(params, [delta], [1], [1.0])
    got = fl_server.aggregate_drfl(params, [delta], [1], [1.0],
                                   staleness=[0], staleness_decay=0.5)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_aggregate_drfl_stale_update_damped_per_layer():
    params, delta = _tiny_params_and_delta()
    fresh = fl_server.aggregate_drfl(params, [delta], [1], [1.0],
                                     staleness=[0])
    stale = fl_server.aggregate_drfl(params, [delta], [1], [1.0],
                                     staleness=[3], staleness_decay=0.5)
    alpha = fl_server.staleness_scale(3, 0.5)
    # held layers: the applied step shrinks by exactly alpha (absolute
    # FedAsync damping, not renormalized away)
    for gp, f, s in zip(jax.tree.leaves(params["stem"]),
                        jax.tree.leaves(fresh["stem"]),
                        jax.tree.leaves(stale["stem"])):
        np.testing.assert_allclose(np.asarray(s - gp),
                                   alpha * np.asarray(f - gp), rtol=1e-5)
    # layers outside the submodel stay untouched either way
    for gp, s in zip(jax.tree.leaves(params["stages"][3]),
                     jax.tree.leaves(stale["stages"][3])):
        np.testing.assert_array_equal(np.asarray(gp), np.asarray(s))


# ---------------------------------------------------------------------------
# collision-free client-update seeds (ISSUE 2 satellite)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_async_bench_256_acceptance():
    """ISSUE 2 acceptance: at n=256 the async engine finishes the same
    simulated-time budget as sync with strictly lower straggler wait."""
    from benchmarks.async_bench import main
    r = main(n=256)
    assert r["async"]["sim_time_total"] <= r["horizon"] + 1e-6
    assert r["async"]["idle_time"] < r["sync"]["idle_time"]
    assert r["async"]["n_tasks"] > 0


def test_client_update_seed_collision_free():
    # the old mix (seed*1000 + t*100 + i) collided whenever i >= 100:
    # (t=0, i=100) == (t=1, i=0).  The SeedSequence mix must not.
    seeds = {fl_client.client_update_seed(0, t, i)
             for t in range(40) for i in range(300)}
    assert len(seeds) == 40 * 300
    # and distinct base seeds do not collide either on a spot-check grid
    other = {fl_client.client_update_seed(1, t, i)
             for t in range(40) for i in range(300)}
    assert not (seeds & other)
