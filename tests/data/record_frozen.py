"""Re-record the frozen trajectory pins kept in this directory.

    PYTHONPATH=src python tests/data/record_frozen.py [--out-dir DIR]

Each pin file holds a run configuration and the sync and async
trajectories ``run_simulation`` gives for it; ``tests/test_energy_profiles``
and ``tests/test_family_contract`` compare against them bit-for-bit.  Those
numbers depend on the installed JAX/XLA (the order of float reductions), so
each file also records the JAX version that produced it.  Re-record only
when the toolchain changed and the program did not, and show that in the
change's notes: the code that first recorded a pin, run under the new
toolchain, must give the same numbers as the current code.
"""
from __future__ import annotations

import argparse
import json
import os

import jax

from repro.fl import FLConfig, run_simulation

HERE = os.path.dirname(os.path.abspath(__file__))
PINS = ("frozen_energy_n8.json", "frozen_transformer_n8.json")
MODES = ("sync", "async")


def trajectory(config: dict, mode: str) -> dict:
    h = run_simulation(FLConfig(**{**config, "engine_mode": mode}),
                       verbose=False)
    return {
        "acc_mean": [float(x) for x in h["acc_mean"]],
        "energy": [float(x) for x in h["energy"]],
        "reward": [float(x) for x in h["reward"]],
        "participants": [[int(i) for i in p] for p in h["participants"]],
        "model_choices": [[int(m) for m in c] for c in h["model_choices"]],
        "sim_time": [float(x) for x in h["sim_time"]],
        "alive": [int(a) for a in h["alive"]],
        "dropouts": int(h["dropouts"]),
    }


def record(name: str, out_dir: str) -> str:
    with open(os.path.join(HERE, name)) as fh:
        config = json.load(fh)["config"]
    pin = {"config": config, "jax_version": jax.__version__}
    for mode in MODES:
        pin[mode] = trajectory(config, mode)
    path = os.path.join(out_dir, name)
    with open(path, "w") as fh:
        json.dump(pin, fh, indent=1)
        fh.write("\n")
    return path


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out-dir", default=HERE)
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)
    for name in PINS:
        print(record(name, args.out_dir))


if __name__ == "__main__":
    main()
