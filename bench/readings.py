#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/<workload>.json`` are set
from, taken at the cell's own size on the chip, in one process:

* the program: the window's first ``CHECK_ROUNDS`` rounds (no warm-up, no
  timing) against the float32 reference, on every seed given;
* the controls, on the first ``--control`` seeds, each put in the
  program's place against the float32 reference: ``control``, the
  reference with parameters, activations and updates in bfloat16, and
  ``mixed``, float32 parameters and updates with every pass in bfloat16;
* a planted fault, ``half_batch``: the reference with each step
  averaging over half its batch, on the same seeds.

    python bench/readings.py --workload resnet18-sync-n100 \
        --seeds 11 12 13 14 15 16 17 18 19 20 21 22 --control 3

``--sides`` picks which of the controls and the fault to read.

Prints one JSON line per reading and a summary line: the program's
largest reading of each number, and the control's and the fault's
smallest.  A step that returns its state unchanged reads exactly 1 on the
two norm gaps and needs no run.  Without a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import jax.numpy as jnp

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


#: the reference's settings for each control and planted fault
SIDES = {"control": {"dtype": jnp.bfloat16},
         "mixed": {"compute_dtype": jnp.bfloat16},
         "half_batch": {"half_batch": True}}


def readings(loaded, seeds, n_control, sides=SIDES, out=sys.stdout):
    import run as bench_run
    from harness import check as chk
    from harness.drive import Runner
    from reference import fl as ref_fl
    from repro.fl import FLConfig

    rows = []
    for j, seed in enumerate(seeds):
        fl = bench_run.fl_settings(loaded, seed)
        dep = loaded["traffic"]["deployment_seed"]
        drv = Runner(FLConfig(**fl), seconds=0.0, deployment_seed=dep,
                     warmup=False)
        rec = drv.run()
        program = bench_run._program_record(rec)
        decisions = [(c.participants, c.model_choice, c.k) for c in rec.check]
        drv.release()
        del drv, rec
        gc.collect()
        model = loaded["reference"]
        want = ref_fl.Reference(model, fl, seed, dep).run(decisions)
        read = [("program", program)]
        if j < n_control:
            read += [(side, ref_fl.Reference(model, fl, seed, dep, **kw)
                      .run(decisions))
                     for side, kw in SIDES.items() if side in sides]
        for side, got in read:
            numbers = chk.compare(got, want, ref_fl.BATTERY_J)
            row = {"side": side, "seed": seed, **numbers}
            rows.append(row)
            print(json.dumps(row), file=out, flush=True)
        gc.collect()
    summary = {}
    for name in chk.NUMBERS:
        for side, pick in [("program", max)] + [(c, min) for c in SIDES]:
            vals = [r[name] for r in rows if r["side"] == side]
            if vals:
                summary[f"{side}.{name}"] = pick(vals)
    print(json.dumps({"summary": summary}), file=out, flush=True)
    return rows, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--sides", nargs="+", choices=list(SIDES),
                    default=list(SIDES))
    args = ap.parse_args(argv)
    import run as bench_run
    loaded = bench_run.load_cell(args.workload)
    sys.path.insert(0, os.path.join(bench_run.REPO_DIR, "src"))
    from repro.compile_cache import enable_compile_cache
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU found", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    readings(loaded, args.seeds, args.control, args.sides)
    return 0


if __name__ == "__main__":
    sys.exit(main())
