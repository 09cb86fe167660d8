"""Paper Table 2 (RQ4): DR-FL accuracy vs server validation-set ratio
(1%%-10%%; paper finds ~4%% optimal — more validation data steals training
data from clients)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import FAST, bench_params, emit
from repro.fl import FLConfig, run_simulation

RATIOS = (0.02, 0.04, 0.08) if FAST else (0.01, 0.02, 0.04, 0.06, 0.08, 0.10)


def main(seed=0, verbose=False):
    p = bench_params()
    accs = {}
    for r in RATIOS:
        t0 = time.time()
        cfg = FLConfig(method="drfl", selector="marl", seed=seed,
                       n_val_fraction=r, alpha=0.1, marl_episodes=2, **p)
        h = run_simulation(cfg, verbose=verbose)
        accs[r] = float(np.mean(h["best_acc"]))
        emit(f"table2/ratio{int(r * 100)}pct", (time.time() - t0) * 1e6,
             f"best_acc_mean={accs[r]:.3f}")
    best = max(accs, key=accs.get)
    emit("table2/optimum", 0.0, f"best_ratio={best};acc={accs[best]:.3f}")
    return accs


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(verbose=True)
