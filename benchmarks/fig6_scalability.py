"""Paper Fig. 6: learning curves / final accuracy for different fleet sizes
(RQ3 scalability).  Directional claim: DR-FL's advantage does not degrade —
and typically grows — with more heterogeneous devices.

Fleet sizes are overridable for large-scale runs (the vectorized FleetState
engine handles 256+ devices):

    REPRO_FIG6_SIZES=64,256 python -m benchmarks.fig6_scalability
    python -m benchmarks.fig6_scalability 64 256

At 64+ devices the runs use the event-driven async engine (no round
barrier, staleness-aware aggregation) and the training-set size scales
with the fleet so per-device data stays roughly constant — a fixed FAST
n_train starves 256-device Dirichlet splits.

At 1024+ devices the MARL selector runs with the FACTORED QMIX state
(``FLConfig.state_mode="auto"`` resolves to the fixed-width fleet summary
above 256 agents — the flat ``n * OBS_DIM`` state used to OOM-scale the
mixer and replay buffer here) and the row runs a bounded smoke profile:
capped training set, one pre-training episode, a small async task budget
(env-tunable via REPRO_FIG6_MAX_TRAIN / REPRO_FIG6_EPISODES /
REPRO_FIG6_BUDGET).  Those rows validate the factored selector and the
data-parallel fleet kernels at scale; the DIRECTIONAL accuracy claim is
carried by the <= 256-device rows.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchmarks.common import FAST, bench_params, emit, family_supports
from repro.fl import FLConfig, run_simulation

SIZES = (8, 14) if FAST else (10, 20, 40)


def _env_sizes():
    raw = os.environ.get("REPRO_FIG6_SIZES", "")
    if not raw:
        return None
    try:
        return tuple(int(s) for s in raw.replace(",", " ").split())
    except ValueError as e:
        raise SystemExit(
            f"REPRO_FIG6_SIZES must be comma/space-separated ints, "
            f"got {raw!r}") from e


def main(seed=0, verbose=False, sizes=None):
    sizes = tuple(sizes) if sizes else (_env_sizes() or SIZES)
    p = bench_params()
    results = {}
    for n in sizes:
        for method, sel in (("drfl", "marl"), ("heterofl", "greedy")):
            if not family_supports(p, method):
                emit(f"fig6/{method}/n{n}", 0.0,
                     f"skipped=unsupported_by_{p['model_family']}")
                continue
            t0 = time.time()
            # at large fleets keep the paper's 10% participation so k (and
            # the per-round training cost) stays proportionate
            overrides = {"n_devices": n}
            # data budget scales with the fleet: a fixed n_train starves
            # 256-device Dirichlet splits (most devices get ~0 samples and
            # the directional gap disappears); hold per-device data roughly
            # constant relative to the base config instead
            overrides["n_train"] = max(
                p["n_train"], int(round(p["n_train"] * n / p["n_devices"])))
            if n >= 64:
                overrides["participation"] = min(p.get("participation", 0.1),
                                                 0.1)
                # scalability runs use the event-driven engine: no round
                # barrier, staleness-aware aggregation (ISSUE 2 default);
                # reward evals once per ~k aggregations, not per arrival —
                # per-event evals would dominate wall-clock at 256 devices
                overrides["engine_mode"] = "async"
                overrides["async_eval_every"] = max(1, int(round(0.1 * n)))
            episodes = 3
            if n >= 1024:
                # bounded smoke profile (see module docstring): the factored
                # selector + data-parallel kernels at fleet scale, not the
                # directional accuracy claim
                overrides["participation"] = min(
                    overrides.get("participation", 0.1), 0.02)
                k = max(1, int(round(overrides["participation"] * n)))
                overrides["n_train"] = min(
                    overrides["n_train"],
                    int(os.environ.get("REPRO_FIG6_MAX_TRAIN", 60000)))
                overrides["async_task_budget"] = int(
                    os.environ.get("REPRO_FIG6_BUDGET", 2 * k))
                overrides["async_eval_every"] = k
                # thousands of per-client jits would compile one program per
                # distinct Dirichlet shard size; the bucketed executor's
                # pow2-padded programs are the only sane path at this scale
                overrides["client_executor"] = "batched"
                episodes = int(os.environ.get("REPRO_FIG6_EPISODES", 1))
            cfg = FLConfig(**{**p, **overrides}, method=method,
                           selector=sel, seed=seed, marl_episodes=episodes)
            h = run_simulation(cfg, verbose=verbose)
            acc = float(np.mean(h["best_acc"]))
            results[(n, method)] = acc
            emit(f"fig6/{method}/n{n}", (time.time() - t0) * 1e6,
                 f"best_acc_mean={acc:.3f}")
    for n in sizes:
        if (n, "drfl") in results and (n, "heterofl") in results:
            emit(f"fig6/gap/n{n}", 0.0,
                 f"drfl_minus_heterofl="
                 f"{results[(n, 'drfl')] - results[(n, 'heterofl')]:.3f}")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cli_sizes = tuple(int(a) for a in sys.argv[1:]) or None
    main(verbose=True, sizes=cli_sizes)
