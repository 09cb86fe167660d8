#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chip it is started on.

    python bench/run.py --workload resnet18-sync-n100 --seed 7 \
        --seconds 30 --trace 0

``--trace 0`` measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` is a run of its own that profiles a few
rounds and reports the per-layer metrics.  Both check the rounds the
window ran against the plain reference.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and the
compared numbers with their limits under ``checks``); the same numbers
are the last lines of standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from harness import files  # noqa: E402

#: where a traced run keeps its profile (inside the checkout, gitignored)
TRACE_DIR = os.path.join(BENCH_DIR, ".runs", "trace")


def _say(msg):
    print(msg, file=sys.stderr, flush=True)


def load_cell(name, spec=None):
    """Everything a run of workload ``name`` needs, found by name."""
    spec = spec or files.benchmark()
    cell = files.workload(spec, name)
    conf, ref_mod = files.config(cell["config"])
    return {"cell": cell, "config": conf, "reference": ref_mod,
            "traffic": files.traffic(cell["traffic"]),
            "limits": files.limits(name),
            "per_layer": [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]}


def fl_settings(loaded, seed):
    return {**loaded["config"]["fl"], **loaded["traffic"]["fl"],
            "seed": int(seed)}


def _program_record(rec):
    """The window's first rounds as a RunRecord (host arrays)."""
    import jax
    import numpy as np
    from reference.fl import RoundRecord, RunRecord
    params = jax.device_get(rec.params0)
    out = RunRecord(params0=params, accs0=rec.accs0, rounds=[])
    for cr in rec.check:
        if cr.params is not None:
            params = jax.device_get(cr.params)
        remaining, ok = jax.device_get(cr.charge)
        out.rounds.append(RoundRecord(
            losses=dict(cr.losses), params=params, accs=cr.accs,
            remaining=np.asarray(remaining, np.float64),
            ok=np.asarray(ok, bool)))
    return out


def check(loaded, fl, rec):
    """Numbers of the window's first rounds against the plain reference,
    and the verdict."""
    from harness import check as chk
    from reference import fl as ref_fl
    run = _program_record(rec)
    decisions = [(cr.participants, cr.model_choice, cr.k) for cr in rec.check]
    ref = ref_fl.Reference(loaded["reference"], fl, fl["seed"],
                           loaded["traffic"]["deployment_seed"])
    want = ref.run(decisions)
    numbers = chk.compare(run, want, ref_fl.BATTERY_J)
    _say("readings " + " ".join(f"{k}={v!r}" for k, v in numbers.items()))
    return chk.verdict(numbers, loaded["limits"])


def per_layer(loaded, rec, cfg, device_kind):
    from harness import trace as tr
    trace = tr.load(rec.trace_file)
    ctx = {"trace": trace, "record": rec, "cfg": cfg,
           "config": loaded["config"], "reference": loaded["reference"],
           "peaks": files.peaks(device_kind), "kernel_cost": files.kernel_cost,
           "tr": tr}
    metrics = {}
    for m in loaded["per_layer"]:
        value = files.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": [[k, v] for k, v in tr.top_ops(trace)],
                 "idle_gaps": [[k, v] for k, v in tr.idle_by_host(trace)]}
    return metrics, breakdown, tr.busy_s(trace), trace.window_s


def run_cell(loaded, seed, seconds, trace, t_process=T_PROCESS,
             warmup=True):
    """One run of a cell on whatever JAX finds; returns the result dict.
    ``main`` has already made sure that is a TPU."""
    import jax
    from harness.drive import Runner
    from repro.fl import FLConfig
    fl = fl_settings(loaded, seed)
    cfg = FLConfig(**fl)
    drv = Runner(cfg, seconds=seconds,
                 deployment_seed=loaded["traffic"]["deployment_seed"],
                 trace_dir=TRACE_DIR if trace else None, warmup=warmup)
    rec = drv.run()
    _say(f"window: rounds={rec.rounds} seconds={rec.window_s:.3f} "
         f"warmup_rounds={rec.warmup_rounds} capped={rec.capped} "
         f"compiles_in_window={rec.compiles_in_window} "
         f"traces_in_window={rec.traces_in_window} "
         f"{' '.join(rec.compiled_in_window)}")
    # a set-up that compiled afresh (the first run in a checkout) is
    # reported apart from one that loaded every program from the cache
    cold = rec.setup_cache_misses > 0
    _say(f"setup: cold_cache={cold} cache_misses={rec.setup_cache_misses} "
         f"compile_and_load_s={rec.setup_compile_s:.3f}")
    devices = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": rec.rounds, "failed": 0,
              "setup": {"cold_cache": cold,
                        "cache_misses": rec.setup_cache_misses,
                        "compile_and_load_s": rec.setup_compile_s}}
    if trace:
        metrics, breakdown, busy, window = per_layer(
            loaded, rec, cfg, devices[0].device_kind)
        device.update(busy_s=busy, window_s=window)
        result["attempted"] = len(rec.traced_rounds)
    else:
        metrics = {
            "setup_s": {"value": rec.t_open - t_process, "unit": "s"},
            "rounds_per_s": {"value": rec.rounds / rec.window_s,
                             "unit": "rounds/s"},
            "peak_hbm_gib": {"value": peak / 2 ** 30, "unit": "GiB"},
        }
    drv.release()
    gc.collect()
    correct, rows = check(loaded, fl, rec)
    result.update(correct=bool(correct), metrics=metrics, device=device)
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload)
    sys.path.insert(0, os.path.join(REPO_DIR, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        _say(f"bench: the system under test is missing ({e})")
        return 2
    import jax
    devices = jax.devices()
    need = int(loaded["cell"]["chips"])
    if devices[0].platform != "tpu":
        _say(f"bench: no TPU found (platform {devices[0].platform!r})")
        return 3
    if len(devices) < need:
        _say(f"bench: the cell needs {need} chips, JAX found {len(devices)}")
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(loaded, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        _say(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
