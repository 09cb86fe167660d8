"""Fleet-engine microbench (the ISSUE-1 ≥10x claim): one DR-FL round's
selection + energy step — price every (device, model) pair, build the
affordability mask, charge the fleet — as the per-device Python loop over
DeviceState (reference semantics) vs the vectorized FleetState kernels.

Both FleetState backends are measured: numpy (float64, zero dispatch
overhead — the CPU winner at n=256: ~25x) and jax/jit (wins as n grows and
on accelerators; at small n the per-call dispatch dominates).

All paths are pure (no fleet mutation), so iterations are comparable.
Emits `fleet/<path>/n<N>` timings plus `fleet/speedup*/n<N>`."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import FAST, emit
from repro.core.energy import make_fleet, round_cost
from repro.core.fleet import (FleetState, fleet_affordability,
                              fleet_affordability_jit, fleet_charge,
                              fleet_charge_jit, fleet_round_cost)

SIZES_B = (2.8e6, 8.4e6, 22.5e6, 44.8e6)
FRACS = (0.11, 0.3, 0.72, 1.0)
NS = (256,) if FAST else (256, 1024, 4096)


def _ref_step(devs):
    """Scalar path: affordability mask + model-0 charge outcome, loop."""
    n, M = len(devs), len(SIZES_B)
    avail = np.zeros((n, M + 1), bool)
    avail[:, M] = True
    rem = np.empty(n)
    alive = np.empty(n, bool)
    for i, d in enumerate(devs):
        if not d.alive:
            rem[i], alive[i] = d.remaining, False
            continue
        need0 = 0.0
        for m in range(M):
            _, _, e_tra, e_com = round_cost(d, SIZES_B[m], FRACS[m])
            avail[i, m] = (e_tra + e_com) < d.remaining
            if m == 0:
                need0 = e_tra + e_com
        if d.remaining <= need0:
            rem[i], alive[i] = 0.0, False
        else:
            rem[i], alive[i] = d.remaining - need0, True
    return avail, rem, alive


def _vec_step_jax(fleet, need_model0, active):
    avail = fleet_affordability_jit(fleet, SIZES_B, FRACS, 5, 32)
    new_fleet, ok = fleet_charge_jit(fleet, need_model0, active)
    return avail, new_fleet, ok


def _vec_step_numpy(fleet, need_model0, active):
    avail = fleet_affordability(fleet, SIZES_B, FRACS, 5, 32)
    new_fleet, ok = fleet_charge(fleet, need_model0, active)
    return avail, new_fleet, ok


def _time(fn, iters):
    fn()  # warmup / compile
    t0 = time.time()
    for _ in range(iters):
        out = fn()
        jax.tree.map(lambda x: jax.block_until_ready(x)
                     if isinstance(x, jax.Array) else x, out)
    return (time.time() - t0) / iters * 1e6


def main():
    for n in NS:
        devs = make_fleet(n, seed=0)
        f_np = FleetState.from_devices(devs, backend="numpy")
        f_jx = FleetState.from_devices(devs, backend="jax")
        _, _, e_tra, e_com = fleet_round_cost(f_np, SIZES_B[0], FRACS[0])
        need_np = e_tra + e_com
        need_jx = jnp.asarray(need_np, jnp.float32)
        act_np = np.ones(n, bool)
        act_jx = jnp.ones(n, bool)
        iters = 3 if n > 1000 else 20
        us_ref = _time(lambda: _ref_step(devs), iters)
        us_np = _time(lambda: _vec_step_numpy(f_np, need_np, act_np),
                      iters * 10)
        us_jx = _time(lambda: _vec_step_jax(f_jx, need_jx, act_jx), iters)
        emit(f"fleet/loop_ref/n{n}", us_ref, f"devices={n};models=4")
        emit(f"fleet/vectorized_numpy/n{n}", us_np, f"devices={n};models=4")
        emit(f"fleet/vectorized_jax/n{n}", us_jx, f"devices={n};models=4")
        emit(f"fleet/speedup_numpy/n{n}", 0.0,
             f"x{us_ref / max(us_np, 1e-9):.1f}")
        emit(f"fleet/speedup_jax/n{n}", 0.0,
             f"x{us_ref / max(us_jx, 1e-9):.1f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
