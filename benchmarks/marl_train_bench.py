"""QMIX *training* scaling bench: flat vs set mixer up to n=1M agents.

PR 5 made the *selection* step O(n/shards); this bench measures the other
half — the MARL TRAINING loop (replay fill + jitted QMIX update) — across
fleet sizes, flat hypernet mixer vs the permutation-invariant set/attention
mixer with sampled-agent replay (``repro.core.marl.networks``).  Each
measured row actually TRAINS: the replay buffer is filled from real
``MarlSelector.select`` episodes over a sampled fleet, then timed gradient
steps run until the smoke horizon, asserting the TD loss decreases.

The flat mixer's hypernet emits one weight row per agent
(``hyper_w1: state_dim -> n*embed``), so at n=65536 its parameters alone
are ~0.5 GB and at n=1M ~8.4 GB (x~5 live copies with target net + Adam
moments + grads) — those rows are recorded as ``skipped`` with the
analytic estimate instead of OOM-killing the bench.  The set mixer's cost
is bounded by the sampled-agent budget, so its per-step time is flat in n
(THE acceptance criterion: set rows at 65536 and 1M match the 4096 row
within noise).

Results land in ``BENCH_marl_train.json``:

    PYTHONPATH=src python -m benchmarks.marl_train_bench            # full
    PYTHONPATH=src python -m benchmarks.marl_train_bench --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import statistics
import sys
import time

SIZES_FULL = (256, 4096, 65536, 1_048_576)
SIZES_SMOKE = (256, 4096)
#: flat-mixer rows above this agent count are recorded analytically, not
#: run: hyper_w1 alone is n*embed^2 floats and the learner holds ~5 live
#: copies (params, target, grads, 2 Adam moments)
FLAT_MAX_MEASURED_N = 4096
EPISODE_LEN = 4            # selector rounds per replay episode
N_EPISODES = 3             # replay episodes filled before timing
OUT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_marl_train.json")


def _peak_rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _flat_analytic_row(n: int) -> dict:
    """Why the flat mixer cannot train here, in bytes."""
    from repro.core.marl.qmix import QmixConfig
    from repro.core.selection import OBS_DIM

    embed = QmixConfig.__dataclass_fields__["mixer_embed"].default
    hyper_w1_floats = embed * (n * embed)      # the O(n) hypernet output row
    live_copies = 5                            # params/target/grads/Adam m+v
    replay_mb = 64 * (EPISODE_LEN + 1) * n * OBS_DIM * 4 / 1e6
    return {
        "n": n, "mode": "flat", "skipped": True,
        "why": "flat hypernet mixer is O(n): params alone exceed memory",
        "hyper_w1_gb_analytic": round(hyper_w1_floats * 4 / 1e9, 2),
        "learner_gb_analytic": round(
            live_copies * hyper_w1_floats * 4 / 1e9, 2),
        "replay_mb_analytic_64ep": round(replay_mb, 1),
    }


def _bench_one(n: int, mixer_mode: str, iters: int, seed: int = 0,
               agent_budget: int = 4096) -> dict:
    import jax
    import numpy as np

    from repro.core.fleet import sample_fleet_state
    from repro.core.marl.buffer import ReplayBuffer
    from repro.core.selection import OBS_DIM, MarlSelector

    model_sizes = (2.8e6, 8.4e6, 22.5e6, 44.8e6)
    model_fracs = (0.11, 0.3, 0.72, 1.0)
    k = max(1, n // 100)
    n_rounds = EPISODE_LEN

    # factored state at every n: this bench isolates the MIXER axis (the
    # flat state was already measured out in BENCH_fleet_shard / PR 5)
    sel = MarlSelector(n, len(model_sizes), n_rounds, seed=seed,
                       state_mode="factored", mixer_mode=mixer_mode,
                       agent_budget=agent_budget)
    budget = agent_budget if mixer_mode == "set" else None
    buf = ReplayBuffer(8, n_rounds, n, OBS_DIM,
                       sel.learner.cfg.state_dim, seed, agent_budget=budget)

    # --- replay fill: real select() episodes over a sampled fleet --------
    t_fill0 = time.time()
    for ep in range(N_EPISODES):
        fleet = sample_fleet_state(n, seed=seed + ep, backend="jax")
        sel.reset_episode()
        for t in range(n_rounds):
            sel.select(fleet, t, k, model_sizes, model_fracs)
            sel.observe_reward(0.1 * (ep + t))
        buf.add_episode(*sel.episode_arrays(fleet, n_rounds))
    fill_s = time.time() - t_fill0

    # --- timed training steps -------------------------------------------
    losses = []

    def step():
        batch = buf.sample(sel.learner.cfg.batch_size)
        losses.append(sel.learner.update(batch)["td_loss"])

    t0 = time.time()
    step()                                     # compile + warm
    compile_s = time.time() - t0
    # smoke training horizon: enough gradient steps for the TD loss to
    # come down from its cold-start value before the timed window
    for _ in range(12):
        step()
    times = []
    for _ in range(iters):
        t0 = time.time()
        step()
        times.append(time.time() - t0)

    return {
        "n": n, "mode": mixer_mode, "skipped": False,
        "agents_stored": buf.N, "iters": iters,
        "train_step_s": round(statistics.median(times), 4),
        "train_step_min_s": round(min(times), 4),
        "compile_s": round(compile_s, 2),
        "replay_fill_s": round(fill_s, 2),
        "replay_mb": round(buf.nbytes / 1e6, 2),
        "peak_rss_mb": round(_peak_rss_mb(), 1),
        "loss_first": round(float(losses[0]), 4),
        "loss_last": round(float(losses[-1]), 4),
        "loss_decreased": bool(losses[-1] < losses[0]),
        "state_dim": sel.learner.cfg.state_dim,
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: n in (256, 4096), fewer iters")
    ap.add_argument("--sizes", type=int, nargs="*", default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--agent-budget", type=int, default=4096)
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.common import emit

    sizes = tuple(args.sizes) if args.sizes else (
        SIZES_SMOKE if args.smoke else SIZES_FULL)
    out = {
        "bench": "marl_train",
        "backend": jax.default_backend(),
        "episode_len": EPISODE_LEN,
        "agent_budget": args.agent_budget,
        "rows": [],
    }
    for n in sorted(sizes):
        iters = args.iters or (3 if (args.smoke or n >= 65536) else 5)
        for mode in ("flat", "set"):
            if mode == "flat" and n > FLAT_MAX_MEASURED_N:
                row = _flat_analytic_row(n)
                out["rows"].append(row)
                print(f"marl_train/flat/n{n}: skipped "
                      f"(analytic learner size "
                      f"{row['learner_gb_analytic']} GB)")
                continue
            row = _bench_one(n, mode, iters,
                             agent_budget=args.agent_budget)
            out["rows"].append(row)
            emit(f"marl_train/{mode}/n{n}", row["train_step_s"] * 1e6,
                 f"agents_stored={row['agents_stored']} "
                 f"replay_mb={row['replay_mb']} "
                 f"loss {row['loss_first']}->{row['loss_last']} "
                 f"peak_rss_mb={row['peak_rss_mb']}")
    if not args.no_write:
        path = os.path.abspath(OUT_JSON)
        existing = {}
        if os.path.exists(path):
            with open(path) as fh:
                existing = json.load(fh)
        if args.smoke and existing.get("rows"):
            # CI smoke must not clobber the recorded full-scale rows
            existing["smoke"] = {"rows": out["rows"],
                                 "backend": out["backend"]}
            out = existing
        else:
            # merge by (n, mode): a partial --sizes rerun must not erase
            # the expensive 65536/1M rows
            fresh = {(r["n"], r["mode"]) for r in out["rows"]}
            out["rows"] += [r for r in existing.get("rows", [])
                            if (r["n"], r["mode"]) not in fresh]
            out["rows"].sort(key=lambda r: (r["n"], r["mode"]))
            if "smoke" in existing:
                out["smoke"] = existing["smoke"]
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"wrote {path}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
