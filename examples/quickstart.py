"""Quickstart: a 5-minute DR-FL run on one CPU core.

    PYTHONPATH=src python examples/quickstart.py

Runs a small fleet of battery-powered heterogeneous devices training the
4-exit layer-wise ResNet with MARL dual-selection, and prints the round-by-
round accuracy / energy / fleet-survival trace.
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.fl import FLConfig, run_simulation


def main():
    cfg = FLConfig(
        n_devices=8,          # heterogeneous fleet (small/medium/large tiers)
        n_rounds=8,
        participation=0.4,    # Top-K = 3 clients per round
        local_epochs=2,
        method="drfl",
        selector="marl",      # the paper's QMIX dual-selection
        alpha=0.5,            # Dirichlet non-IID
        n_train=1200,
        energy_scale=0.05,    # make the battery budget binding
        seed=0,
    )
    print(f"DR-FL quickstart: {cfg.n_devices} devices, {cfg.n_rounds} rounds, "
          f"alpha={cfg.alpha}, selector={cfg.selector}")
    hist = run_simulation(cfg, verbose=True)
    print("\nbest accuracy per layer-wise model (Models 1-4):",
          np.round(hist["best_acc"], 3))
    print("devices alive at end:", hist["alive"][-1], "/", cfg.n_devices)
    print("total energy remaining: %.0f J" % hist["energy"][-1])


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
