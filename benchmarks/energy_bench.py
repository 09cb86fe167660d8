"""Energy-scenario grid: selectors x charge/availability/budget scenarios.

Runs the full FL loop (``repro.fl.run_simulation``) for every selector in
{marl, greedy, random, static} under the four energy scenarios the
:mod:`repro.energy` subsystem ships —

* ``constant``       — the static-battery baseline (no recharge),
* ``solar``          — phase-shifted sinusoid harvesting,
* ``diurnal``        — day/night availability waves (duty 0.5),
* ``global_budget``  — a fleet-wide joule ceiling over a harvest-backed
  (solar) fleet: the budget meters what the fleet may *attempt*, sized so
  a wasteful selector burns through it,

at n in {256, 4096} devices (Top-K held at ~8 tasks per round via the
participation fraction and per-device shards held constant via
``n_train = 3n``, so the training work per round is size-invariant and
the grid finishes on CPU; MARL auto-switches to the factored QMIX state
above the flat-state cutoff).

``ENERGY_SCALE`` makes batteries BIND: a fresh battery (~19 J) affords
the small submodels everywhere, the mid tier (~12-26 J) only on part of
the fleet, and the largest (~46-104 J) nowhere — so selection quality
decides who survives.  Affordability-blind selection (random) routinely
assigns a submodel its device cannot pay for — ``fleet_charge`` semantics
say the device attempts anyway, wastes its whole remaining battery, and
dies (the paper's useless-training arm) — while the affordability-masked
selectors never take a lethal pick.  Under harvesting the gap compounds:
dead devices stop harvesting, so every kill also forfeits its future
charge; under the budget, lethal and oversized attempts burn shared
joules (~4-6x the masked selectors' spend rate) for zero accuracy
contribution.

Per cell: final mean exit accuracy, surviving devices, net joules drained,
and **joules per accuracy point** (net drain / 100*acc) — the paper's
energy-awareness figure of merit.  The JSON also records the directional
claims the tests/README cite: MARL beats random on joules-per-accuracy-
point under solar harvesting and under the global budget.  MARL cells
pre-train the QMIX policy for ``marl_episodes=3`` (the fig5 precedent);
the deciding mechanism above is the affordability mask, so the claims are
robust to the accuracy noise floor of CPU-scale synthetic runs.

    PYTHONPATH=src python -m benchmarks.energy_bench            # full grid
    PYTHONPATH=src python -m benchmarks.energy_bench --smoke    # n=256, CI

Results land in ``BENCH_energy.json`` at the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.fl import FLConfig, run_simulation

OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_energy.json")

SELECTORS = ("marl", "greedy", "random", "static")
K_TARGET = 8                    # tasks per round, size-invariant
ENERGY_SCALE = 0.0025           # fresh battery ~19 J: small submodels fit
#                                 everywhere, mid tier (~12-26 J) only on
#                                 the strongest devices, largest never
DAY = 3600.0                    # scenario day length, sim-seconds
BUDGET_PER_PICK = 18.0          # J of shared budget per scheduled pick —
#                                 ~4x the disciplined (affordability-masked)
#                                 per-pick cost but below the ~24 J/pick an
#                                 affordability-blind selector attempts, so
#                                 the cap binds on waste, not on discipline


def scenario_fields(name: str, n: int, n_rounds: int) -> dict:
    """Flat-config field group for one named scenario."""
    if name == "constant":
        return {}
    if name == "solar":
        return dict(charge_profile="solar", charge_rate=2.0,
                    charge_period=DAY)
    if name == "diurnal":
        return dict(availability_profile="diurnal", availability_duty=0.5,
                    charge_period=DAY)
    if name == "global_budget":
        # a shared joule ceiling over a harvest-backed fleet, sized in
        # ABSOLUTE terms from the scheduled pick work (k picks/round —
        # which is n-invariant here — NOT from the fleet's total charge,
        # which would stop binding as n grows): enough to fund every round
        # at mid-submodel cost, not enough to waste on lethal attempts
        return dict(charge_profile="solar", charge_rate=2.0,
                    charge_period=DAY,
                    global_budget_j=BUDGET_PER_PICK * K_TARGET * n_rounds)
    raise ValueError(name)


SCENARIOS = ("constant", "solar", "diurnal", "global_budget")


def run_cell(scenario: str, selector: str, n: int, n_rounds: int,
             seed: int = 0, verbose: bool = False) -> dict:
    cfg = FLConfig(n_devices=n, n_rounds=n_rounds,
                   participation=K_TARGET / n, n_train=3 * n,
                   local_epochs=1, method="drfl", selector=selector,
                   energy_scale=ENERGY_SCALE, seed=seed,
                   marl_episodes=3 if selector == "marl" else 1,
                   **scenario_fields(scenario, n, n_rounds))
    t0 = time.time()
    h = run_simulation(cfg, verbose=verbose)
    e_start = n * 7560.0 * ENERGY_SCALE
    joules = max(e_start - float(h["energy"][-1]), 0.0)
    acc = float(h["acc_mean"][-1])
    row = {
        "scenario": scenario, "selector": selector, "n": n,
        "rounds_run": len(h["acc_mean"]), "final_acc": acc,
        "surviving": int(h["alive"][-1]), "dropouts": int(h["dropouts"]),
        "joules": joules,
        "joules_per_acc_point": joules / max(100.0 * acc, 1e-9),
        "terminated": h["terminated"]["reason"],
        "wall_s": round(time.time() - t0, 1),
    }
    if "budget" in h:
        row["budget_limit"] = h["budget"]["limit"]
        row["budget_spent"] = h["budget"]["spent"]
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: n=256 only, no JSON rewrite")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=OUT_JSON)
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    sizes = (256,) if args.smoke else (256, 4096)
    # same round count in both modes: the selector gaps (kills forfeiting
    # future harvest, budget burn) need a few rounds to compound
    n_rounds = args.rounds or 8

    rows = []
    for n in sizes:
        for scenario in SCENARIOS:
            for selector in SELECTORS:
                row = run_cell(scenario, selector, n, n_rounds,
                               seed=args.seed, verbose=args.verbose)
                rows.append(row)
                print(f"{scenario:14s} {selector:7s} n={n:5d} "
                      f"acc={row['final_acc']:.3f} "
                      f"alive={row['surviving']:5d} "
                      f"J={row['joules']:8.1f} "
                      f"J/acc-pt={row['joules_per_acc_point']:7.2f} "
                      f"[{row['terminated']}] {row['wall_s']}s",
                      flush=True)

    def jpap(scenario, selector, n):
        for r in rows:
            if (r["scenario"], r["selector"], r["n"]) == (scenario,
                                                          selector, n):
                return r["joules_per_acc_point"]
        return None

    claims = {}
    for scenario in ("solar", "global_budget"):
        for n in sizes:
            m, r = jpap(scenario, "marl", n), jpap(scenario, "random", n)
            claims[f"marl_beats_random_jpap/{scenario}/n{n}"] = (
                m is not None and r is not None and m < r)
    for k, v in claims.items():
        print(f"claim {k}: {v}")

    if not args.smoke:
        out = {
            "bench": "energy_scenarios",
            "k_target": K_TARGET, "energy_scale": ENERGY_SCALE,
            "n_rounds": n_rounds, "seed": args.seed,
            "rows": rows, "claims": claims,
        }
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        print(f"wrote {os.path.abspath(args.out)}")
    return rows, claims


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
