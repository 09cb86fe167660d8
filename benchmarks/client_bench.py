"""Per-client loop vs bucketed-vmap executor: dispatch count + wall time.

Times the full per-round CLIENT-UPDATE + AGGREGATION hot path of a sync
DR-FL round (selection, energy accounting and evaluation excluded — they
are identical under both executors) for the two paths:

* ``perclient`` — one jit dispatch per participant per mini-batch, a
  per-client delta reduction and host loss sync, then the list-based
  ``aggregate_drfl`` (eager tree.map over ~90 leaves x N clients);
* ``batched``   — repro.fl.batch: participants bucketed by submodel index,
  each bucket ONE vmap(scan) jit program (<= 4 program executions per
  round, mini-batches gathered device-side), deltas fed STACKED into the
  one-program ``aggregate_drfl_stacked`` (Pallas ``layer_agg`` on TPU,
  fused einsum on CPU).

The configuration is the CPU-budget large-fleet regime (8x8 images,
0.06-width backbone, batch 8) where per-op overhead dominates per-step
FLOPs —
the regime ``client_executor="auto"`` picks the batched path for (on CPU,
execution of paper-width models is BLAS-bound and auto keeps them
per-client; see ``repro.fl.engine.resolve_client_executor``).

Repeat rounds keep the cohort membership fixed and rotate the per-round
client seeds (fresh schedules each round, same padded shapes), so the
timed rounds measure the steady state a long run amortizes to; program
compile counts are reported separately (``batched_compiles_warm``) —
cohort churn re-compiles only when a bucket's pow2-padded (P, T) signature
is new.

Every registered model family runs the same harness (``--family cnn``
limits the sweep) over its OWN corpus (``family.make_dataset`` — image
rows for cnn/mlp, token windows for the transformer); BENCH_client.json
records per-family medians with a ``family`` field per row — the CNN rows
keep the PR 3 emit names and configuration, so its numbers stay
regression-comparable.

    python -m benchmarks.client_bench                 # n=64/256/1024 sweep
    python -m benchmarks.client_bench --smoke         # n=64, 2 rounds (CI)
    python -m benchmarks.client_bench --family mlp    # one family only
    python -m benchmarks.client_bench --json OUT.json # record results

The ISSUE 3 acceptance targets >= 5x at n=256 on CPU with <= 4
client-update program executions per round.  The dispatch bound holds
everywhere; measured wall-time speedup on the 2-core container is ~2.5-4x
median (bursts to ~5.8x unloaded) — per-client execution there is already
op-work-bound inside XLA, so the remaining gap is arithmetic, not
dispatch.  BENCH_client.json records the medians for future PRs to
regress against.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.data.partition import dirichlet_partition
from repro.fl import batch as fl_batch
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.models.family import get_family

FAMILIES = ("cnn", "mlp", "transformer")
PARTICIPATION = 0.1
EPOCHS = 2
BATCH = 8
LR = 0.05
HW = 8
WIDTH = 0.06
SERVER_LR = 0.7


def _setup(n: int, family: str = "cnn", seed: int = 0):
    fam = get_family(family)
    # family-routed corpus; for image families this is the exact legacy
    # synthetic_image_dataset call (bit-for-bit comparable rows)
    x, y = fam.make_dataset(max(1500, 6 * n), 10, hw=HW, noise=1.0,
                            seed=seed)
    parts = dirichlet_partition(y, n, 0.5, seed)
    params = fam.init(jax.random.PRNGKey(seed), 10, width_mult=WIDTH, hw=HW)
    return x, y, parts, params


def _cohort(n: int, parts, rnd: int, family: str = "cnn", seed: int = 0):
    """Round ``rnd``'s cohort: k non-empty-shard devices with model index
    round-robin over the family's submodels.  Membership (and therefore
    every padded program shape) is fixed across rounds; the per-round seeds
    reshuffle each client's local schedule exactly as the engine does."""
    k = max(1, int(round(PARTICIPATION * n)))
    ids, j = [], 0
    while len(ids) < k and j < n:
        if len(parts[j]):
            ids.append(j)
        j += 1
    ms = [i % get_family(family).num_submodels() for i in ids]
    seeds = [fl_client.client_update_seed(seed, rnd, i) for i in ids]
    return ids, ms, seeds


def round_per_client(params, x, y, parts, ids, ms, seeds, family="cnn"):
    """Legacy hot path: per-client updates + list-based aggregation."""
    deltas, weights = [], []
    for i, m, s in zip(ids, ms, seeds):
        d, _ = fl_client.drfl_client_update(
            params, m, x[parts[i]], y[parts[i]], epochs=EPOCHS, batch=BATCH,
            lr=LR, seed=s, family=family)
        deltas.append(d)
        weights.append(float(len(parts[i])))
    new = fl_server.aggregate_drfl(params, deltas, ms, weights,
                                   server_lr=SERVER_LR, family=family)
    jax.block_until_ready(new)
    return new


def round_batched(params, x_dev, y_dev, parts, ids, ms, seeds, family="cnn"):
    """Bucketed hot path: <= n_buckets executor programs + stacked
    aggregation."""
    res = fl_batch.run_cohort(
        "drfl", params, x_dev, y_dev, [parts[i] for i in ids], ids, ms,
        seeds, epochs=EPOCHS, batch=BATCH, lr=LR, family=family,
        fleet_steps=fl_batch.longest_schedule(parts, epochs=EPOCHS,
                                              batch=BATCH))
    new = fl_server.aggregate_drfl_stacked(
        params, [(b.model_idx, b.stacked_delta, b.weights, None)
                 for b in res.buckets], server_lr=SERVER_LR, family=family)
    jax.block_until_ready(new)
    return new


def bench_one(n: int, rounds: int, family: str = "cnn", seed: int = 0
              ) -> dict:
    x, y, parts, params = _setup(n, family, seed)
    x_dev, y_dev = fl_batch.resident(x, family), jnp.asarray(y)

    # warmup round 0 (compiles both paths) then time rounds 1..R
    ids, ms, seeds = _cohort(n, parts, 0, family, seed)
    round_per_client(params, x, y, parts, ids, ms, seeds, family)
    fl_batch.reset_counters()
    round_batched(params, x_dev, y_dev, parts, ids, ms, seeds, family)
    warm_compiles = fl_batch.COUNTERS["compiles"]

    # per-round MEDIAN wall time: interleaved per-path timing on a small
    # shared-CPU box is noisy, and the per-client path (hundreds of tiny
    # ops) is hit hardest by scheduling jitter
    pc_steps, pc_times, b_times = 0, [], []
    for r in range(1, rounds + 1):
        ids, ms, seeds = _cohort(n, parts, r, family, seed)
        t0 = time.time()
        round_per_client(params, x, y, parts, ids, ms, seeds, family)
        pc_times.append(time.time() - t0)
        pc_steps += sum(
            len(fl_batch.client_schedule(parts[i], s, EPOCHS, BATCH))
            for i, s in zip(ids, seeds))
    t_pc = float(np.median(pc_times))

    fl_batch.reset_counters()
    for r in range(1, rounds + 1):
        ids, ms, seeds = _cohort(n, parts, r, family, seed)
        t0 = time.time()
        round_batched(params, x_dev, y_dev, parts, ids, ms, seeds, family)
        b_times.append(time.time() - t0)
    t_b = float(np.median(b_times))
    execs = fl_batch.COUNTERS["executions"] / rounds
    compiles = fl_batch.COUNTERS["compiles"]

    n_buckets = get_family(family).num_submodels()
    r = {"n": n, "k": len(ids), "rounds": rounds, "family": family,
         "per_client_s_per_round": t_pc,
         "batched_s_per_round": t_b,
         "speedup": t_pc / max(t_b, 1e-12),
         "per_client_dispatches_per_round": pc_steps / rounds + len(ids) + 1,
         "batched_executions_per_round": execs,
         "batched_compiles_steady": compiles,
         "batched_compiles_warm": warm_compiles}
    assert execs <= n_buckets, (execs, n_buckets)
    # CNN keeps its PR 3 emit names so recorded numbers stay comparable
    tag = f"client_bench/n{n}" if family == "cnn"         else f"client_bench/{family}/n{n}"
    emit(tag, t_b * 1e6,
         f"speedup={r['speedup']:.1f}x over per-client "
         f"({t_pc*1e3:.0f}ms -> {t_b*1e3:.0f}ms/round) "
         f"execs/round={execs:.1f} "
         f"pc_dispatches/round={r['per_client_dispatches_per_round']:.0f}")
    return r


def main(argv=None) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    smoke = "--smoke" in argv
    json_out = None
    if "--json" in argv:
        json_out = argv[argv.index("--json") + 1]
    families = ([argv[argv.index("--family") + 1]] if "--family" in argv
                else list(FAMILIES))
    sizes = [64] if smoke else [64, 256, 1024]
    rounds = 2 if smoke else 4
    results = [bench_one(n, rounds, family=fam)
               for fam in families for n in sizes]
    out = {"participation": PARTICIPATION, "epochs": EPOCHS, "batch": BATCH,
           "hw": HW, "width_mult": WIDTH, "families": families,
           "results": results}
    if json_out:
        with open(json_out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {json_out}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
