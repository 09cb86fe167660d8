"""Paper Table 1: test accuracy of DR-FL vs HeteroFL vs ScaleFL across
Dirichlet alpha, per layer-wise model (4 exits), under energy constraints.

Directional claim checked: DR-FL's mean/best accuracy >= the baselines under
the same battery budget (the paper reports DR-FL winning 29/36 cells)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import bench_params, emit, family_supports
from repro.fl import FLConfig, run_simulation

ALPHAS = (0.1, 0.5, 1.0)
# drfl/marl = the paper's full method (QMIX dual-selection; undertrained at
# CPU-scale round counts — see EXPERIMENTS.md §Paper for the caveat);
# drfl/greedy = the DR-FL framework with a greedy policy (selector ablation).
ARMS = (("drfl", "marl"), ("drfl+greedy", None), ("heterofl", "greedy"),
        ("scalefl", "greedy"))


def main(alphas=ALPHAS, seed=0, verbose=False):
    p = bench_params()
    rows = []
    for alpha in alphas:
        for method, sel in ARMS:
            t0 = time.time()
            if method == "drfl+greedy":
                method_, sel_ = "drfl", "greedy"
            else:
                method_, sel_ = method, sel or "greedy"
            if not family_supports(p, method_):
                emit(f"table1/{method}/alpha{alpha}", 0.0,
                     f"skipped=unsupported_by_{p['model_family']}")
                continue
            cfg = FLConfig(alpha=alpha, method=method_, selector=sel_,
                           seed=seed, marl_episodes=4, **p)
            h = run_simulation(cfg, verbose=verbose)
            best = np.asarray(h["best_acc"])
            rows.append((alpha, method, best, time.time() - t0))
            emit(f"table1/{method}/alpha{alpha}", (time.time() - t0) * 1e6,
                 "best_acc_per_exit=" + "|".join(f"{a:.3f}" for a in best))
    # directional summary: DR-FL mean(best exits) vs baselines per alpha
    for alpha in alphas:
        cells = {m: float(np.mean(r)) for a, m, r, _ in rows if a == alpha}
        winner = max(cells, key=cells.get)
        emit(f"table1/winner/alpha{alpha}", 0.0,
             f"winner={winner};" + ";".join(f"{m}={v:.3f}" for m, v in cells.items()))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(verbose=True)
