"""Energy-scenario subsystem (repro.energy): profiles, budgets, parity.

Contracts:
* the DEFAULT scenario (``charge_profile="constant"``, ``charge_rate=0``,
  ``availability_profile="always"``, ``global_budget_j=0``) is bit-for-bit
  identical to the pre-profile engine — pinned against the frozen n=8
  trajectories in ``tests/data/frozen_energy_n8.json`` for BOTH engine
  modes;
* ``EnergySpec`` profile fields survive ``from_flat``/``to_flat`` exactly,
  and invalid names/params raise at construction;
* profile kernels behave: solar clips at zero, the carbon window opens and
  closes with local intensity, diurnal availability waves follow
  ``tz_phase``, and each host twin agrees with its device mask;
* the global joule budget is a HARD constraint for every selector, and
  exhausting it terminates the run with ``reason="budget_exhausted"``;
* infeasible ``energy_scale`` (no fresh device can afford its cheapest
  submodel) raises at build time instead of wiping the fleet in round 0;
* the new per-device arrays ride the kill-and-resume checkpoint contract
  (``FLEET_CHECKPOINT_FIELDS`` covers every FleetState array field).
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core.fleet import make_fleet_state
from repro.energy import (EnergyScenario, get_availability_profile,
                          get_charge_profile, known_availability_profiles,
                          known_charge_profiles, scenario_from_config)
from repro.energy.profiles import (CARBON_INTENSITY_CUTOFF, AlwaysAvailable,
                                   CarbonWindowCharge, ConstantCharge,
                                   DiurnalAvailability, SolarCharge)
from repro.fl import FLConfig, run_simulation
from repro.fl.spec import EnergySpec, SimulationSpec

FROZEN = os.path.join(os.path.dirname(__file__), "data",
                      "frozen_energy_n8.json")


def _np_fleet(n=8, seed=0):
    return make_fleet_state(n, seed, backend="numpy")


def _scenario(**kw):
    kw.setdefault("charge", ConstantCharge())
    kw.setdefault("availability", AlwaysAvailable())
    return EnergyScenario(**kw)


# ---------------------------------------------------------------------------
# registries + spec surface
# ---------------------------------------------------------------------------


def test_registries_know_the_builtin_profiles():
    assert set(known_charge_profiles()) >= {"constant", "solar",
                                            "carbon_window"}
    assert set(known_availability_profiles()) >= {"always", "diurnal"}
    with pytest.raises(ValueError, match="unknown charge profile"):
        get_charge_profile("fusion")
    with pytest.raises(ValueError, match="unknown availability profile"):
        get_availability_profile("sometimes")


def test_energy_spec_validates_profiles():
    with pytest.raises(ValueError, match="charge_profile"):
        EnergySpec(charge_profile="fusion")
    with pytest.raises(ValueError, match="availability_profile"):
        EnergySpec(availability_profile="sometimes")
    with pytest.raises(ValueError, match="charge_rate"):
        EnergySpec(charge_rate=-1.0)
    with pytest.raises(ValueError, match="charge_period"):
        EnergySpec(charge_period=0.0)
    with pytest.raises(ValueError, match="availability_duty"):
        EnergySpec(availability_duty=0.0)
    with pytest.raises(ValueError, match="availability_duty"):
        EnergySpec(availability_duty=1.5)
    with pytest.raises(ValueError, match="global_budget_j"):
        EnergySpec(global_budget_j=-5.0)


def test_energy_spec_round_trips_through_flat_config():
    cfg = FLConfig(n_devices=4, n_rounds=2, charge_profile="solar",
                   charge_rate=3.5, charge_period=1234.0,
                   availability_profile="diurnal", availability_duty=0.4,
                   global_budget_j=777.0)
    spec = SimulationSpec.from_flat(cfg)
    assert spec.energy.charge_profile == "solar"
    assert spec.energy.charge_rate == 3.5
    assert spec.energy.charge_period == 1234.0
    assert spec.energy.availability_profile == "diurnal"
    assert spec.energy.availability_duty == 0.4
    assert spec.energy.global_budget_j == 777.0
    back = spec.to_flat()
    for f in dataclasses.fields(FLConfig):
        assert getattr(back, f.name) == getattr(cfg, f.name), f.name


def test_scenario_from_config_resolves_profiles():
    cfg = FLConfig(n_devices=4, n_rounds=2, charge_profile="carbon_window",
                   charge_rate=2.0, charge_period=500.0,
                   availability_profile="diurnal", availability_duty=0.3)
    sc = scenario_from_config(cfg)
    assert isinstance(sc.charge, CarbonWindowCharge)
    assert sc.charge.period == 500.0
    assert isinstance(sc.availability, DiurnalAvailability)
    assert sc.availability.duty == 0.3
    assert not sc.is_trivial
    # the default config is the trivial scenario — no hooks run at all
    assert scenario_from_config(FLConfig(n_devices=4, n_rounds=2)).is_trivial


# ---------------------------------------------------------------------------
# profile kernels
# ---------------------------------------------------------------------------


def test_solar_rate_is_clipped_sinusoid():
    fleet = _scenario(charge=SolarCharge(period=100.0),
                      charge_rate=4.0).init_fleet(_np_fleet(), seed=7)
    prof = SolarCharge(period=100.0)
    tz = np.asarray(fleet.tz_phase, np.float64)
    amp = np.asarray(fleet.charge_rate, np.float64)
    for t in (0.0, 13.0, 37.5, 80.0):
        want = amp * np.maximum(np.sin(2 * np.pi * (t / 100.0 + tz)), 0.0)
        np.testing.assert_allclose(prof.rate(fleet, t), want, rtol=1e-6)
    # night side of every phase is exactly zero, never negative
    assert (prof.rate(fleet, 0.0) >= 0.0).all()


def test_carbon_window_gates_and_reopens():
    prof = CarbonWindowCharge(period=100.0)
    tz = np.zeros(1)
    # local midnight: intensity 0 -> open, full charge rate
    assert prof.ok_host(tz, 0.0).all()
    # local peak (t = period/2): intensity 1 -> blocked, zero charge
    assert not prof.ok_host(tz, 50.0).any()
    fleet = _np_fleet(1, seed=1).replace(charge_rate=np.ones(1),
                                         tz_phase=np.zeros(1))
    np.testing.assert_allclose(prof.rate(fleet, 50.0), [0.0], atol=1e-12)
    # next_ok from the blocked peak lands exactly where the gate reopens
    t_open = float(prof.next_ok_host(tz, 50.0)[0])
    assert t_open > 50.0
    assert prof.ok_host(tz, t_open + 1e-6).all()
    assert not prof.ok_host(tz, t_open - 1.0).any()
    # already-open devices report "now"
    assert float(prof.next_ok_host(tz, 0.0)[0]) == 0.0


def test_diurnal_availability_follows_local_day():
    prof = DiurnalAvailability(period=100.0, duty=0.5)
    tz = np.array([0.0, 0.5])          # one device half a day offset
    assert list(prof.available_host(tz, 10.0)) == [True, False]
    assert list(prof.available_host(tz, 60.0)) == [False, True]
    # device-side mask agrees with the host twin
    fleet = _np_fleet(2, seed=2).replace(tz_phase=tz.copy())
    np.testing.assert_array_equal(prof.available(fleet, 10.0),
                                  prof.available_host(tz, 10.0))
    # a blocked device's next opening is the start of its next local day
    nxt = prof.next_available_host(tz, 60.0)
    assert float(nxt[0]) == pytest.approx(100.0)
    assert float(nxt[1]) == 60.0


def test_scenario_availability_combines_wave_and_carbon_gate():
    sc = _scenario(charge=CarbonWindowCharge(period=100.0),
                   availability=DiurnalAvailability(period=100.0, duty=0.6),
                   charge_rate=1.0)
    assert not sc.trivial_availability
    tz = np.array([0.0])
    fleet = _np_fleet(1, seed=3).replace(tz_phase=tz.copy(),
                                         charge_rate=np.ones(1))
    for t in (5.0, 30.0, 50.0, 70.0, 95.0):
        av = sc.available(fleet, t)
        host = sc.available_host(tz, t)
        np.testing.assert_array_equal(np.asarray(av), host)
        # the AND of the two gates, by hand
        want = ((t / 100.0 % 1.0) < 0.6) and (
            0.5 - 0.5 * np.cos(2 * np.pi * t / 100.0)
            <= CARBON_INTENSITY_CUTOFF)
        assert bool(host[0]) == want, t
    # wake time is strictly in the future when the gate is shut
    t_wake = sc.next_available_host(tz, 70.0)
    assert t_wake > 70.0


def test_apply_charge_caps_and_never_resurrects():
    fleet = _np_fleet(3, seed=4)
    sc = _scenario(charge_rate=10.0, energy_scale=0.01)
    fleet = sc.init_fleet(fleet, seed=4)
    cap = np.asarray(fleet.battery) * 0.01
    low = cap * 0.1
    fleet = fleet.replace(remaining=low.copy(),
                          alive=np.array([True, True, False]))
    out = sc.apply_charge(fleet, 0.0, 1e9)   # absurdly long: must hit cap
    rem = np.asarray(out.remaining)
    np.testing.assert_allclose(rem[:2], cap[:2], rtol=1e-6)
    assert rem[2] == low[2]                  # dead device holds its charge
    # zero-length interval is the identity
    assert sc.apply_charge(fleet, 5.0, 5.0) is fleet


def test_init_fleet_is_seed_stable_across_scenarios():
    f1 = _scenario(charge=SolarCharge(), charge_rate=2.0).init_fleet(
        _np_fleet(16, seed=9), seed=9)
    f2 = _scenario(charge=CarbonWindowCharge(), charge_rate=2.0).init_fleet(
        _np_fleet(16, seed=9), seed=9)
    # same seed -> same phases, whatever the profile
    np.testing.assert_array_equal(f1.tz_phase, f2.tz_phase)
    np.testing.assert_array_equal(f1.charge_rate, f2.charge_rate)
    assert (np.asarray(f1.tz_phase) >= 0).all()
    assert (np.asarray(f1.tz_phase) < 1).all()


# ---------------------------------------------------------------------------
# checkpoint coverage of the new arrays
# ---------------------------------------------------------------------------


def test_checkpoint_fields_cover_profile_arrays():
    from repro.checkpoint.io import FLEET_CHECKPOINT_FIELDS
    from repro.core.fleet import _ARRAY_FIELDS
    assert set(FLEET_CHECKPOINT_FIELDS) == set(_ARRAY_FIELDS)
    assert {"charge_rate", "tz_phase"} <= set(FLEET_CHECKPOINT_FIELDS)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

_BASE = dict(n_devices=8, n_rounds=6, participation=0.5, n_train=600,
             local_epochs=1, method="drfl", selector="marl",
             energy_scale=0.05, seed=3)


def test_infeasible_energy_scale_raises_at_build():
    cfg = FLConfig(**{**_BASE, "energy_scale": 1e-5})
    with pytest.raises(ValueError, match="cheapest submodel"):
        run_simulation(cfg, verbose=False)


@pytest.mark.parametrize("selector", ["random", "greedy", "static", "marl"])
def test_global_budget_is_a_hard_constraint(selector):
    cfg = FLConfig(**{**_BASE, "selector": selector, "n_rounds": 4,
                      "n_train": 400}, global_budget_j=150.0)
    h = run_simulation(cfg, verbose=False)
    assert h["budget"]["limit"] == 150.0
    assert h["budget"]["spent"] <= 150.0 + 1e-6
    if h["terminated"]["reason"] == "budget_exhausted":
        assert h["terminated"]["budget"] == "energy"


def test_budget_exhaustion_terminates_async():
    # the budget is half of what the same run spends unconstrained, so the
    # joule budget (not the task budget) must be what ends the run
    base = FLConfig(**{**_BASE, "n_rounds": 6, "n_train": 400},
                    engine_mode="async", global_budget_j=1e12)
    free = run_simulation(base, verbose=False)
    assert free["terminated"]["budget"] == "tasks"
    limit = 0.5 * free["budget"]["spent"]
    cfg = dataclasses.replace(base, global_budget_j=limit)
    h = run_simulation(cfg, verbose=False)
    assert h["budget"]["spent"] <= limit + 1e-6
    assert h["terminated"]["reason"] == "budget_exhausted"
    assert h["terminated"]["budget"] == "energy"


def test_solar_recharge_extends_the_fleet():
    base = FLConfig(**{**_BASE, "n_rounds": 4, "n_train": 400})
    solar = dataclasses.replace(base, charge_profile="solar",
                                charge_rate=5.0)
    h0 = run_simulation(base, verbose=False)
    h1 = run_simulation(solar, verbose=False)
    # harvesting strictly adds energy on the same trajectory of picks
    assert h1["energy"][-1] > h0["energy"][-1]


def test_diurnal_availability_gates_participants():
    # duty so small every device is offline most of its day; period longer
    # than the run so the mask is static: only the ~duty fraction of
    # devices whose local morning overlaps t=0 may ever participate
    cfg = FLConfig(**{**_BASE, "n_rounds": 3, "n_train": 400},
                   availability_profile="diurnal", availability_duty=0.25,
                   charge_period=1e9)
    h = run_simulation(cfg, verbose=False)
    sc = scenario_from_config(cfg)
    from repro.fl import build_world
    wfleet = build_world(cfg).fleet
    open_now = np.flatnonzero(
        sc.available_host(np.asarray(wfleet.tz_phase, np.float64), 0.0))
    seen = {i for p in h["participants"] for i in p}
    assert seen <= set(open_now.tolist())


# ---------------------------------------------------------------------------
# bit-for-bit parity: default scenario vs the frozen trajectories
# ---------------------------------------------------------------------------


def _assert_frozen(mode):
    with open(FROZEN) as fh:
        ref = json.load(fh)
    cfg = FLConfig(**{**ref["config"], "engine_mode": mode,
                      # explicit defaults: the trivial scenario spelled out
                      "charge_profile": "constant",
                      "availability_profile": "always",
                      "global_budget_j": 0.0})
    h = run_simulation(cfg, verbose=False)
    r = ref[mode]
    np.testing.assert_array_equal(np.asarray(h["acc_mean"]), r["acc_mean"])
    np.testing.assert_array_equal(np.asarray(h["energy"]), r["energy"])
    np.testing.assert_array_equal(np.asarray(h["reward"]), r["reward"])
    np.testing.assert_array_equal(np.asarray(h["sim_time"]), r["sim_time"])
    assert [list(p) for p in h["participants"]] == r["participants"]
    assert [list(m) for m in h["model_choices"]] == r["model_choices"]
    assert list(h["alive"]) == r["alive"]
    assert h["dropouts"] == r["dropouts"]


def test_default_scenario_bit_for_bit_sync():
    _assert_frozen("sync")


def test_default_scenario_bit_for_bit_async():
    _assert_frozen("async")
