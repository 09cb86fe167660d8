"""Sharded FleetState (repro.sharding.fleet) vs single-placement parity.

Most tests here need a multi-device runtime; the shard-smoke CI job (and
``benchmarks/fleet_shard_bench.py``) force one on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Under the default
single-device tier-1 run they skip — except the subprocess test at the
bottom, which spawns a fresh interpreter with the flag set so the
sharded-vs-unsharded equivalence contract is exercised by tier-1 too
(``slow``-marked: it pays a second jax startup).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.fleet import (fleet_affordability_jit, fleet_charge_jit,
                              fleet_summary_jit, make_fleet_state)
from repro.sharding.fleet import (FLEET_AXIS, fleet_mesh, fleet_spec_for,
                                  is_sharded, maybe_shard_fleet, shard_fleet,
                                  unshard_fleet)

SIZES = (2.8e6, 8.4e6, 22.5e6, 44.8e6)
FRACS = (0.11, 0.3, 0.72, 1.0)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs a multi-device runtime (shard-smoke CI sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def test_maybe_shard_noop_below_two_shards():
    fleet = make_fleet_state(8, seed=0, backend="jax")
    assert maybe_shard_fleet(fleet, 0) is fleet
    assert maybe_shard_fleet(fleet, 1) is fleet
    assert not is_sharded(fleet)


def test_explicit_mesh_larger_than_device_count_raises():
    fleet = make_fleet_state(8, seed=0, backend="jax")
    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="devices exist"):
        maybe_shard_fleet(fleet, too_many)
    with pytest.raises(ValueError, match="devices exist"):
        fleet_mesh(too_many)
    # -1 = all local devices: unsharded on a one-device runtime
    if len(jax.devices()) == 1:
        assert maybe_shard_fleet(fleet, -1) is fleet


def test_single_device_mesh_spec():
    mesh = fleet_mesh(1)
    # trivially divisible: the fleet axis still names the placement
    assert fleet_spec_for("remaining", (32,), mesh) == \
        jax.sharding.PartitionSpec(FLEET_AXIS)
    assert fleet_spec_for("scalar", (), mesh) == jax.sharding.PartitionSpec()


@multi_device
def test_shard_fleet_placement_and_divisibility_fallback():
    mesh = fleet_mesh()
    n_dev = mesh.shape[FLEET_AXIS]
    fleet = shard_fleet(make_fleet_state(16 * n_dev, 0, backend="jax"), mesh)
    assert is_sharded(fleet)
    assert len(fleet.remaining.sharding.device_set) == n_dev
    # indivisible fleet dim falls back to replication instead of erroring
    odd = shard_fleet(make_fleet_state(16 * n_dev + 1, 0, backend="jax"),
                      mesh)
    assert odd.remaining.sharding.is_fully_replicated
    # round-trip back to host numpy
    back = unshard_fleet(fleet)
    assert isinstance(back.remaining, np.ndarray)
    np.testing.assert_array_equal(back.remaining,
                                  np.asarray(fleet.remaining))


@multi_device
def test_sharded_kernels_match_single_placement():
    n = 32 * len(jax.devices())
    single = make_fleet_state(n, seed=5, backend="jax")
    single = single.replace(remaining=single.battery * 0.05)
    sharded = shard_fleet(single, fleet_mesh())

    aff_s = np.asarray(fleet_affordability_jit(single, SIZES, FRACS, 5, 32))
    aff_p = np.asarray(fleet_affordability_jit(sharded, SIZES, FRACS, 5, 32))
    np.testing.assert_array_equal(aff_s, aff_p)

    need = np.linspace(0.0, 400.0, n).astype(np.float32)
    active = (np.arange(n) % 3 != 1)
    f_s, ok_s = fleet_charge_jit(single, need, active)
    f_p, ok_p = fleet_charge_jit(sharded, need, active)
    assert is_sharded(f_p)                 # sharding survives the kernel
    np.testing.assert_array_equal(np.asarray(ok_s), np.asarray(ok_p))
    np.testing.assert_allclose(np.asarray(f_s.remaining),
                               np.asarray(f_p.remaining), rtol=1e-6)

    s_s = np.asarray(fleet_summary_jit(single, SIZES, FRACS, 2, n_rounds=10))
    s_p = np.asarray(fleet_summary_jit(sharded, SIZES, FRACS, 2,
                                       n_rounds=10))
    np.testing.assert_allclose(s_s, s_p, rtol=1e-5, atol=1e-6)


@multi_device
def test_sharded_dual_selection_step_equivalence():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.marl.networks import agent_hidden_init, agent_init
    from repro.core.selection import OBS_DIM, dual_selection_energy_step_jit
    mesh = fleet_mesh()
    n = 64 * mesh.shape[FLEET_AXIS]
    fleet = make_fleet_state(n, seed=2, backend="jax")
    params = agent_init(jax.random.PRNGKey(0), OBS_DIM, len(SIZES) + 1)
    hidden = agent_hidden_init(n)
    args = (SIZES, FRACS)

    f1, h1, part1, act1, sum1 = dual_selection_energy_step_jit(
        params, hidden, fleet, *args, k=8, n_rounds=10)
    f2, h2, part2, act2, sum2 = dual_selection_energy_step_jit(
        params, jax.device_put(hidden, NamedSharding(mesh, P(FLEET_AXIS))),
        shard_fleet(fleet, mesh), *args, k=8, n_rounds=10)
    np.testing.assert_array_equal(np.asarray(part1), np.asarray(part2))
    np.testing.assert_array_equal(np.asarray(act1), np.asarray(act2))
    np.testing.assert_allclose(np.asarray(f1.remaining),
                               np.asarray(f2.remaining), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sum1), np.asarray(sum2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-5, atol=1e-6)


@multi_device
def test_engine_runs_on_sharded_fleet():
    """fleet_mesh=-1 threads through build_world: the whole sync engine
    runs with the fleet row-sharded (host code gathers transparently)."""
    from repro.fl import FLConfig, run_simulation
    from repro.fl.engine import build_world
    cfg = FLConfig(n_devices=16, n_rounds=2, participation=0.5, n_train=400,
                   local_epochs=1, method="drfl", selector="greedy", seed=0,
                   fleet_mesh=-1)
    assert is_sharded(build_world(cfg).fleet)
    h = run_simulation(cfg)
    ref = run_simulation(FLConfig(**{**cfg.__dict__, "fleet_mesh": 0}))
    assert h["participants"] == ref["participants"]
    assert h["acc_mean"] == ref["acc_mean"]


@multi_device
def test_trained_set_selector_step_matches_on_mesh():
    """A TRAINED set-mixer selector's jitted update is placement-invariant:
    the same replay batch produces the same loss/params whether the
    episode was traced against a single-placement or a mesh-sharded fleet
    (shard_agent_array handles the companion [n, ...] arrays)."""
    from repro.core.marl.buffer import ReplayBuffer
    from repro.core.selection import OBS_DIM, MarlSelector
    from repro.sharding.fleet import shard_agent_array

    mesh = fleet_mesh()
    n = 64 * mesh.shape[FLEET_AXIS]

    def run(shard):
        sel = MarlSelector(n, len(SIZES), n_rounds=3, seed=0,
                           state_mode="factored", mixer_mode="set",
                           agent_budget=16)
        fleet = make_fleet_state(n, seed=2, backend="jax")
        if shard:
            fleet = shard_fleet(fleet, mesh)
            sel.hidden = shard_agent_array(sel.hidden, mesh)
        buf = ReplayBuffer(4, 3, n, OBS_DIM, sel.learner.cfg.state_dim, 0,
                           agent_budget=16)
        for t in range(3):
            sel.select(fleet, t, 8, SIZES, FRACS)
            sel.observe_reward(1.0)
        buf.add_episode(*sel.episode_arrays(fleet, 3))
        return sel.learner.update(buf.sample(4))["td_loss"]

    loss_single, loss_sharded = run(False), run(True)
    np.testing.assert_allclose(loss_single, loss_sharded,
                               rtol=1e-5, atol=1e-6)


@multi_device
def test_shard_agent_array_placement_and_fallback():
    from repro.sharding.fleet import shard_agent_array
    mesh = fleet_mesh()
    n_dev = mesh.shape[FLEET_AXIS]
    x = np.zeros((16 * n_dev, 64), np.float32)
    placed = shard_agent_array(x, mesh)
    assert len(placed.sharding.device_set) == n_dev
    assert not placed.sharding.is_fully_replicated
    odd = shard_agent_array(np.zeros((16 * n_dev + 1, 64), np.float32), mesh)
    assert odd.sharding.is_fully_replicated


def test_dual_selection_step_one_executable_per_shape():
    """The sharded hot-path step must reuse ONE executable across rounds of
    the same shape (round_idx is traced, k/n_rounds are static) — the
    runtime complement to the static retrace-hazard lint rule."""
    from repro.analysis.runtime import cache_size, compile_guard
    from repro.core.marl.networks import agent_hidden_init, agent_init
    from repro.core.selection import OBS_DIM, dual_selection_energy_step_jit

    n = 16
    fleet = make_fleet_state(n, seed=3, backend="jax")
    params = agent_init(jax.random.PRNGKey(0), OBS_DIM, len(SIZES) + 1)
    f, h, *_ = dual_selection_energy_step_jit(
        params, agent_hidden_init(n), fleet, SIZES, FRACS, k=4,
        round_idx=0, n_rounds=8)
    if cache_size(dual_selection_energy_step_jit) == 0:
        pytest.skip("jit wrapper does not expose _cache_size")
    with compile_guard(dual_selection_energy_step_jit, max_new=0):
        for r in range(1, 5):
            f, h, *_ = dual_selection_energy_step_jit(
                params, h, f, SIZES, FRACS, k=4, round_idx=r, n_rounds=8)
    # a NEW fleet shape is allowed exactly one fresh executable
    with compile_guard(dual_selection_energy_step_jit, max_new=1):
        dual_selection_energy_step_jit(
            params, agent_hidden_init(2 * n),
            make_fleet_state(2 * n, seed=4, backend="jax"), SIZES, FRACS,
            k=4, round_idx=0, n_rounds=8)


# ---------------------------------------------------------------------------
# tier-1 coverage under the default single-device runtime
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_equivalence_in_forced_multidevice_subprocess():
    """Spawns a fresh interpreter with a forced 4-device CPU mesh and runs
    the kernel-equivalence checks there, so tier-1 exercises the sharded
    path even though this process owns a single device."""
    code = textwrap.dedent("""
        import numpy as np, jax
        assert len(jax.devices()) == 4, jax.devices()
        from repro.core.fleet import make_fleet_state, fleet_summary_jit, \\
            fleet_charge_jit
        from repro.sharding.fleet import fleet_mesh, shard_fleet, is_sharded
        SIZES = (2.8e6, 8.4e6, 22.5e6, 44.8e6)
        FRACS = (0.11, 0.3, 0.72, 1.0)
        single = make_fleet_state(64, seed=5, backend="jax")
        sharded = shard_fleet(single, fleet_mesh())
        assert is_sharded(sharded)
        s1 = np.asarray(fleet_summary_jit(single, SIZES, FRACS, 1,
                                          n_rounds=4))
        s2 = np.asarray(fleet_summary_jit(sharded, SIZES, FRACS, 1,
                                          n_rounds=4))
        np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-6)
        need = np.linspace(0, 300, 64).astype(np.float32)
        f1, ok1 = fleet_charge_jit(single, need, np.ones(64, bool))
        f2, ok2 = fleet_charge_jit(sharded, need, np.ones(64, bool))
        np.testing.assert_array_equal(np.asarray(ok1), np.asarray(ok2))
        np.testing.assert_allclose(np.asarray(f1.remaining),
                                   np.asarray(f2.remaining), rtol=1e-6)
        print("SHARDED-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED-OK" in out.stdout
