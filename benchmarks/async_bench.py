"""Sync barrier vs async event engine: straggler wait at scale.

Runs the same DR-FL configuration twice — ``engine_mode="sync"`` and
``engine_mode="async"`` with the sync run's total simulated time as the
async time horizon and the sync-equivalent client-task budget — and
reports, per engine:

* ``sim_time``  — virtual makespan to finish the task budget;
* ``idle``      — straggler wait: how long finished client updates sat
  before entering the global model (the barrier cost; zero-by-construction
  for per-event aggregation, but computed rather than assumed);
* ``tasks`` / ``aggs`` and the async staleness profile.

The acceptance claim (ISSUE 2): at n=256 the async engine finishes the
same simulated-time budget with strictly lower idle time than sync.

    python -m benchmarks.async_bench            # n=256 (also under FAST)
    python -m benchmarks.async_bench 64         # override fleet size
    REPRO_ASYNC_N=512 python -m benchmarks.async_bench
"""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np

from benchmarks.common import FAST, Timer, emit
from repro.fl import FLConfig, run_simulation


def base_config(n: int, seed: int = 0) -> FLConfig:
    # tiny data/energy budget: the comparison is about SCHEDULING (virtual
    # time and idle), not accuracy, so client updates stay cheap even at 256
    return FLConfig(n_devices=n, n_rounds=2 if FAST else 8,
                    participation=0.1, n_train=max(1500, 6 * n),
                    local_epochs=1, method="drfl", selector="greedy",
                    seed=seed, energy_scale=0.05)


def main(n: int = 0, seed: int = 0, verbose: bool = False):
    n = int(n or os.environ.get("REPRO_ASYNC_N", 0) or 256)
    cfg = base_config(n, seed)

    with Timer() as tm:
        h_sync = run_simulation(dataclasses.replace(cfg, engine_mode="sync"),
                                verbose=verbose)
    emit(f"async_bench/sync/n{n}", tm.dt * 1e6,
         f"sim_time={h_sync['sim_time_total']:.1f}s "
         f"idle={h_sync['idle_time']:.1f}s aggs={h_sync['n_aggregations']}")

    horizon = h_sync["sim_time_total"]
    with Timer() as tm:
        h_async = run_simulation(
            dataclasses.replace(cfg, engine_mode="async",
                                async_time_horizon=horizon),
            verbose=verbose)
    stale = np.asarray(h_async["staleness"]) if h_async["staleness"] else \
        np.zeros(1)
    emit(f"async_bench/async/n{n}", tm.dt * 1e6,
         f"sim_time={h_async['sim_time_total']:.1f}s "
         f"idle={h_async['idle_time']:.1f}s tasks={h_async['n_tasks']} "
         f"aggs={h_async['n_aggregations']} "
         f"staleness_mean={stale.mean():.2f} staleness_max={stale.max()}")
    emit(f"async_bench/gap/n{n}", 0.0,
         f"idle_sync_minus_async={h_sync['idle_time'] - h_async['idle_time']:.1f}s "
         f"makespan_ratio={h_async['sim_time_total'] / max(horizon, 1e-9):.3f}")
    return {"sync": h_sync, "async": h_async, "horizon": horizon}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main(n=int(sys.argv[1]) if len(sys.argv) > 1 else 0, verbose=True)
