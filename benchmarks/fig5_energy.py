"""Paper Fig. 5: total remaining energy + running time vs round, DR-FL vs
HeteroFL-style greedy, heterogeneous fleet (paper: 20 Nano + 20 Xavier).

Directional claims checked: (a) DR-FL sustains more rounds before devices
exhaust their batteries; (b) DR-FL's cumulative running time grows slower
(less waiting/useless training)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import bench_params, emit, family_supports
from repro.fl import FLConfig, run_simulation


def main(seed=0, verbose=False):
    p = bench_params()
    p["n_rounds"] = max(p["n_rounds"], 10)
    out = {}
    for method, sel in (("drfl", "marl"), ("heterofl", "greedy")):
        if not family_supports(p, method):
            emit(f"fig5/{method}", 0.0,
                 f"skipped=unsupported_by_{p['model_family']}")
            continue
        t0 = time.time()
        cfg = FLConfig(method=method, selector=sel, seed=seed,
                       marl_episodes=3, **p)   # binding battery budget
        h = run_simulation(cfg, verbose=verbose)
        e = np.asarray(h["energy"])
        t = np.cumsum(h["round_time"])
        alive = np.asarray(h["alive"])
        surv = int(np.argmax(alive < alive[0])) if (alive < alive[0]).any() \
            else len(alive)
        out[method] = dict(energy=e, cum_time=t, alive=alive, surv=surv)
        emit(f"fig5/{method}", (time.time() - t0) * 1e6,
             f"rounds_before_first_death={surv};final_energy_J={e[-1]:.0f};"
             f"final_cum_time_s={t[-1]:.1f};alive_end={alive[-1]}")
    if "drfl" in out and "heterofl" in out:
        emit("fig5/claim", 0.0,
             f"drfl_survives_rounds={out['drfl']['surv']}"
             f";heterofl_survives_rounds={out['heterofl']['surv']}"
             f";claim_holds={out['drfl']['surv'] >= out['heterofl']['surv']}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(verbose=True)
