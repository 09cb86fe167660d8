"""Energy-aware federated LM training over the early-exit transformer
family (``model_family="transformer"``, docs/FAMILIES.md).

The paper's dual-selection workflow on a language task: a fleet of
battery-powered devices trains the early-exit decoder on the synthetic
next-token corpus; each round the selector picks WHO participates and
WHICH depth prefix (Model_1..Model_4) each client trains, and the server
layer-align aggregates the zero-filled deltas.

    PYTHONPATH=src python examples/train_lm.py                    # MARL
    PYTHONPATH=src python examples/train_lm.py --selector greedy \
        --rounds 12 --devices 16 --ckpt /tmp/lm.msgpack

``--ckpt`` saves the final global params for ``examples/serve_lm.py``
(early-exit greedy decoding from the same tree).

``--local`` skips the fleet and runs plain local DR-FL client updates on
one simulated device per depth — the smallest possible demo of
``family.client_update`` + ``aggregate_drfl`` without the engine.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.checkpoint import save_pytree
from repro.fl import FLConfig, run_simulation
from repro.fl import server as fl_server
from repro.models.family import get_family


def run_local(args):
    """Engine-free mini round-loop: one client per depth prefix."""
    fam = get_family("transformer")
    x, y = fam.make_dataset(args.n_train, 10, hw=args.seq, noise=1.0,
                            seed=args.seed)
    n_val = max(64, args.n_train // 10)
    x_val, y_val = x[:n_val], y[:n_val]
    x_tr, y_tr = x[n_val:], y[n_val:]
    gp = fam.init(jax.random.PRNGKey(args.seed), 10, width_mult=args.width,
                  hw=args.seq)
    M = fam.num_submodels()
    shards = np.array_split(np.arange(len(x_tr)), M)
    for rnd in range(args.rounds):
        deltas, idxs, weights, losses = [], [], [], []
        for m in range(M):
            sh = shards[m]
            d, loss = fam.client_update(
                "drfl", gp, m, x_tr[sh], y_tr[sh], epochs=args.epochs,
                batch=args.batch, lr=args.lr, seed=args.seed + rnd * M + m)
            deltas.append(d)
            idxs.append(m)
            weights.append(float(len(sh)))
            losses.append(loss)
        gp = fl_server.aggregate_drfl(gp, deltas, idxs, weights,
                                      server_lr=0.7, family=fam)
        accs = np.asarray(fl_server.evaluate(gp, x_val, y_val, family=fam))
        print(f"round {rnd:3d} losses={np.round(losses, 3)} "
              f"exit accs={np.round(accs, 3)}")
    return gp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--selector", default="marl",
                    choices=["marl", "greedy", "random", "static"])
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--participation", type=float, default=0.5)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seq", type=int, default=8,
                    help="context window length (cfg.hw)")
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--n-train", type=int, default=1200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--async", dest="engine_async", action="store_true",
                    help="event-driven async rounds instead of sync barriers")
    ap.add_argument("--local", action="store_true",
                    help="engine-free client_update/aggregate demo")
    ap.add_argument("--ckpt", default=None,
                    help="save final global params (msgpack) for serve_lm")
    args = ap.parse_args(argv)

    if args.local:
        gp = run_local(args)
    else:
        cfg = FLConfig(
            n_devices=args.devices, n_rounds=args.rounds,
            participation=args.participation, local_epochs=args.epochs,
            batch_size=args.batch, lr=args.lr, n_train=args.n_train,
            hw=args.seq, width_mult=args.width, seed=args.seed,
            model_family="transformer", method="drfl",
            selector=args.selector, energy_scale=0.05,
            engine_mode="async" if args.engine_async else "sync")
        t0 = time.time()
        h = run_simulation(cfg, verbose=True)
        print(f"\n{cfg.engine_mode} run: {len(h['acc_mean'])} evals in "
              f"{time.time() - t0:.1f}s wall; final mean exit acc "
              f"{h['acc_mean'][-1]:.3f}, fleet energy left "
              f"{h['energy'][-1]:,.0f} J, dropouts {h['dropouts']}")
        gp = h["params"]

    if args.ckpt:
        save_pytree(args.ckpt, gp)
        print("saved", args.ckpt)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
