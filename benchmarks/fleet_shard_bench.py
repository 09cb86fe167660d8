"""Sharded-fleet scaling bench: selection + energy step past n=4096.

Measures ONE data-parallel MARL dual-selection + energy step
(:func:`repro.core.selection.dual_selection_energy_step`: obs -> shared
agent Q -> affordability-masked actions -> Top-K cut -> Eq. 5/7 charge ->
factored summary; a single jit program) at n in {4096, 65536, 1M} devices,
single-placement vs row-sharded over a ``jax.sharding`` "fleet" mesh
(:mod:`repro.sharding.fleet`).  This establishes the first scaling row past
n=4096 — the flat QMIX state could not even be INSTANTIATED there
(``state_dim = n * OBS_DIM``; factored ``state_dim`` stays
``summary_width``, asserted here and in ``tests/test_factored_state.py``).

On CPU the mesh is virtual: ``--devices N`` forces
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax loads
(so this module must be the process entry point, or the flag must already
be in the environment — the shard-smoke CI job sets it).  On a real
multi-chip host the same code shards over the physical devices.

Peak memory is process peak-RSS (``ru_maxrss``; monotonic, so rows run
small -> large and each row reports the running peak) plus the analytic
per-shard fleet bytes.  Results land in ``BENCH_fleet_shard.json``:

    PYTHONPATH=src python -m benchmarks.fleet_shard_bench            # full
    PYTHONPATH=src python -m benchmarks.fleet_shard_bench --smoke    # CI
    PYTHONPATH=src python -m benchmarks.fleet_shard_bench --fig6     # + one
        REPRO_FIG6_SIZES=4096 factored-selector run folded into the JSON
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import statistics
import sys
import time

SIZES_FULL = (4096, 65536, 1_048_576)
SIZES_SMOKE = (4096,)
K_FRACTION = 0.01          # Top-K participation per step
OUT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_fleet_shard.json")


def _force_host_devices(n: int) -> None:
    """Must run before jax is imported anywhere in this process."""
    if "jax" in sys.modules:
        return                      # too late — use whatever jax has
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _bench_one(n: int, iters: int, sharded: bool, seed: int = 0) -> dict:
    import jax
    import numpy as np

    from repro.core.fleet import (_ARRAY_FIELDS, sample_fleet_state,
                                  summary_width)
    from repro.core.marl.networks import agent_hidden_init, agent_init
    from repro.core.selection import OBS_DIM, dual_selection_energy_step_jit
    from repro.sharding.fleet import (FLEET_AXIS, fleet_mesh,
                                      shard_agent_array, shard_fleet)

    model_sizes = (2.8e6, 8.4e6, 22.5e6, 44.8e6)
    model_fracs = (0.11, 0.3, 0.72, 1.0)
    k = max(1, int(K_FRACTION * n))
    fleet = sample_fleet_state(n, seed=seed, backend="jax")
    params = agent_init(jax.random.PRNGKey(seed), OBS_DIM,
                        len(model_sizes) + 1)
    hidden = agent_hidden_init(n)
    n_shards = 1
    if sharded:
        mesh = fleet_mesh()
        n_shards = mesh.shape[FLEET_AXIS]
        fleet = shard_fleet(fleet, mesh)
        hidden = shard_agent_array(hidden, mesh)

    def step(f, h):
        f, h, part, actions, summ = dual_selection_energy_step_jit(
            params, h, f, model_sizes, model_fracs, k=k, n_rounds=100)
        return f, h, summ

    # compile + warm
    t0 = time.time()
    fleet, hidden, summ = step(fleet, hidden)
    jax.block_until_ready(summ)
    compile_s = time.time() - t0

    times = []
    for _ in range(iters):
        t0 = time.time()
        fleet, hidden, summ = step(fleet, hidden)
        jax.block_until_ready(summ)
        times.append(time.time() - t0)

    fleet_mb = sum(np.asarray(getattr(fleet, f)).nbytes
                   for f in _ARRAY_FIELDS) / 1e6
    return {
        "n": n, "k": k, "mode": "sharded" if sharded else "single",
        "n_shards": n_shards, "iters": iters,
        "step_time_s": round(statistics.median(times), 4),
        "step_time_min_s": round(min(times), 4),
        "compile_s": round(compile_s, 2),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "fleet_mb": round(fleet_mb, 2),
        "fleet_mb_per_shard": round(fleet_mb / n_shards, 2),
        "state_dim_factored": summary_width(len(model_sizes)),
        "state_dim_flat_would_be": n * OBS_DIM,
    }


def _run_fig6_row() -> dict:
    """One REPRO_FIG6_SIZES=4096 factored-selector run (the Fig. 6 fix:
    the flat state OOM-scaled here), folded into the bench JSON."""
    from benchmarks import fig6_scalability
    t0 = time.time()
    results = fig6_scalability.main(sizes=(4096,))
    return {
        "sizes": [4096],
        "wall_s": round(time.time() - t0, 1),
        "best_acc_mean": {f"{m}/n{n}": round(a, 4)
                          for (n, m), a in results.items()},
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual host devices for the fleet mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: n=4096 only, fewer iters")
    ap.add_argument("--sizes", type=int, nargs="*", default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--fig6", action="store_true",
                    help="also run + record a REPRO_FIG6_SIZES=4096 row")
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args(argv)

    _force_host_devices(args.devices)
    import jax

    from benchmarks.common import emit

    sizes = tuple(args.sizes) if args.sizes else (
        SIZES_SMOKE if args.smoke else SIZES_FULL)
    out = {
        "bench": "fleet_shard",
        "backend": jax.default_backend(),
        "host_devices": len(jax.devices()),
        "k_fraction": K_FRACTION,
        "rows": [],
    }
    for n in sorted(sizes):
        iters = args.iters or (3 if (args.smoke or n >= 1_000_000) else 5)
        for sharded in (False, True):
            row = _bench_one(n, iters, sharded)
            out["rows"].append(row)
            emit(f"fleet_shard/{row['mode']}/n{n}",
                 row["step_time_s"] * 1e6,
                 f"shards={row['n_shards']} peak_rss_mb={row['peak_rss_mb']}"
                 f" state_dim={row['state_dim_factored']}")
    if args.fig6:
        out["fig6_n4096"] = _run_fig6_row()
        emit("fleet_shard/fig6/n4096", out["fig6_n4096"]["wall_s"] * 1e6,
             f"best_acc={out['fig6_n4096']['best_acc_mean']}")
    if not args.no_write:
        path = os.path.abspath(OUT_JSON)
        existing = {}
        if os.path.exists(path):
            with open(path) as fh:
                existing = json.load(fh)
        if args.smoke and existing.get("rows"):
            # CI smoke must not clobber the recorded full-scale rows; a
            # fig6 row computed this run still lands
            existing["smoke"] = {k: out[k] for k in ("host_devices", "rows")}
            if "fig6_n4096" in out:
                existing["fig6_n4096"] = out["fig6_n4096"]
            out = existing
        else:
            # full runs refresh what they recomputed but keep previously
            # recorded results: rows merge by (n, mode) — a partial
            # --sizes rerun must not erase the expensive 65536/1M rows —
            # and un-recomputed keys (the ~140s fig6 row) carry over
            fresh = {(r["n"], r["mode"]) for r in out["rows"]}
            out["rows"] += [r for r in existing.get("rows", [])
                            if (r["n"], r["mode"]) not in fresh]
            out["rows"].sort(key=lambda r: (r["n"], r["mode"] != "single"))
            for key in ("fig6_n4096", "smoke"):
                if key in existing and key not in out:
                    out[key] = existing[key]
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"wrote {path}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
