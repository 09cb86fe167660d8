"""Crash-safe soak bench: churn kill/resume parity + checkpoint latency.

Two measurements for the crash-safe fleet service:

* **churn soak** — a short async DR-FL run under seeded fault injection
  (crash / timeout / disconnect / corrupt) with periodic full-engine
  checkpoints; the run is killed right after a save
  (``halt_after_saves``), resumed from disk, and the resumed history +
  global params are asserted **bit-identical** to an uninterrupted
  reference run.  The recorded row is the parity verdict plus the fault
  ledger (events, reaps, quarantines).
* **checkpoint latency** — ``EngineCheckpointer.save``/``load`` on a
  synthetic full-engine state (all :data:`FLEET_CHECKPOINT_FIELDS`
  arrays from :func:`sample_fleet_state`, float64 host mirrors, global
  CNN params) at n in {4096, 65536} devices: median wall seconds and
  on-disk bytes per snapshot.

Results land in ``BENCH_checkpoint.json`` (smoke runs never clobber the
recorded full-scale rows):

    PYTHONPATH=src python -m benchmarks.soak_bench            # full
    PYTHONPATH=src python -m benchmarks.soak_bench --smoke    # CI
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import tempfile
import time

SIZES_FULL = (4096, 65536)
SIZES_SMOKE = (4096,)
OUT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_checkpoint.json")


def _churn_config(smoke: bool):
    from repro.fl import FLConfig
    # full participation + healthy batteries so injected faults land on
    # live, in-flight devices (a dead fleet exercises nothing)
    return FLConfig(n_devices=8, n_rounds=4 if smoke else 8,
                    participation=1.0, local_epochs=1, batch_size=8,
                    n_train=256, hw=8, seed=3, selector="greedy",
                    energy_scale=50.0, engine_mode="async",
                    async_time_horizon=200.0 if smoke else 400.0,
                    fault_crashes=1, fault_timeouts=2,
                    fault_disconnects=1, fault_corrupts=3)


def _hist_fingerprint(hist) -> dict:
    """Canonical bytes of everything parity-relevant in a run history."""
    import hashlib

    import jax
    import numpy as np

    def canon(x):
        if isinstance(x, (np.ndarray, jax.Array)):
            a = np.asarray(x)
            return ["arr", str(a.dtype), a.tobytes().hex()]
        if isinstance(x, dict):
            return {str(k): canon(v) for k, v in sorted(x.items())}
        if isinstance(x, (list, tuple)):
            return [canon(v) for v in x]
        return repr(x)

    digests = {}
    for k in sorted(hist):
        blob = json.dumps(canon(hist[k])).encode()
        digests[k] = hashlib.sha256(blob).hexdigest()
    return digests


def run_churn(smoke: bool) -> dict:
    from repro.checkpoint import CheckpointHalt
    from repro.fl import run_simulation
    cfg = _churn_config(smoke)
    t0 = time.time()
    ref = run_simulation(cfg)
    t_ref = time.time() - t0
    with tempfile.TemporaryDirectory() as d:
        ck = dataclasses.replace(cfg, checkpoint_dir=d, checkpoint_every=2)
        try:
            run_simulation(ck, halt_after_saves=1)
            raise AssertionError("halt_after_saves=1 did not kill the run")
        except CheckpointHalt:
            pass
        t0 = time.time()
        res = run_simulation(dataclasses.replace(ck, resume=True))
        t_res = time.time() - t0
    fa, fb = _hist_fingerprint(ref), _hist_fingerprint(res)
    mismatched = sorted(k for k in fa if fa.get(k) != fb.get(k))
    if mismatched or set(fa) != set(fb):
        raise AssertionError(
            f"kill-and-resume diverged from the uninterrupted run on "
            f"hist keys {mismatched or sorted(set(fa) ^ set(fb))}")
    faults = ref["faults"]
    return {
        "parity": "bit-identical",
        "n_fault_events": len(faults["events"]),
        "n_reaped": faults["n_reaped"],
        "n_quarantined": faults["n_quarantined"],
        "terminated": ref["terminated"]["reason"],
        "vrounds": len(ref["acc_mean"]),
        "ref_wall_s": round(t_ref, 3),
        "resumed_wall_s": round(t_res, 3),
    }


def _synthetic_engine_state(n: int):
    import jax
    import numpy as np

    from repro.checkpoint.io import FLEET_CHECKPOINT_FIELDS
    from repro.core.fleet import sample_fleet_state
    from repro.models import cnn

    fleet = sample_fleet_state(n, seed=0)
    return {
        "mode": "async",
        "fleet": {f: getattr(fleet, f) for f in FLEET_CHECKPOINT_FIELDS},
        "global_params": cnn.init(jax.random.PRNGKey(0), num_classes=10,
                                  width_mult=0.25),
        "busy64": np.zeros(n, np.float64),
        "alive_host": np.ones(n, bool),
        "state": {"version": 7, "seq": 123, "sim_time": 512.25},
    }


def bench_checkpoint(n: int, iters: int) -> dict:
    state = _synthetic_engine_state(n)
    saves, loads = [], []
    with tempfile.TemporaryDirectory() as d:
        from repro.checkpoint import EngineCheckpointer
        ck = EngineCheckpointer(d, keep=2)
        path = None
        for i in range(iters):
            t0 = time.time()
            path = ck.save(state, {"episode": 0, "step": i})
            saves.append(time.time() - t0)
        arrays = path.replace(".manifest.json", ".ckpt")
        nbytes = os.path.getsize(path) + os.path.getsize(arrays)
        for _ in range(iters):
            t0 = time.time()
            restored, _meta = ck.load(path)
            loads.append(time.time() - t0)
        assert restored["fleet"]["battery"].shape[0] == n
    return {
        "n": n,
        "iters": iters,
        "save_s_median": round(statistics.median(saves), 4),
        "load_s_median": round(statistics.median(loads), 4),
        "snapshot_bytes": nbytes,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: n=4096 only, short churn run")
    ap.add_argument("--sizes", type=int, nargs="*", default=None)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--no-write", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.common import emit

    sizes = tuple(args.sizes) if args.sizes else (
        SIZES_SMOKE if args.smoke else SIZES_FULL)
    out = {"bench": "checkpoint", "backend": jax.default_backend(),
           "rows": []}
    for n in sorted(sizes):
        iters = args.iters or (3 if args.smoke else 5)
        row = bench_checkpoint(n, iters)
        out["rows"].append(row)
        emit(f"checkpoint/save/n{n}", row["save_s_median"] * 1e6,
             f"bytes={row['snapshot_bytes']} "
             f"load_s={row['load_s_median']}")
    out["churn"] = run_churn(args.smoke)
    emit("checkpoint/churn", out["churn"]["resumed_wall_s"] * 1e6,
         f"parity={out['churn']['parity']} "
         f"faults={out['churn']['n_fault_events']} "
         f"quarantined={out['churn']['n_quarantined']}")

    if not args.no_write:
        path = os.path.abspath(OUT_JSON)
        existing = {}
        if os.path.exists(path):
            with open(path) as fh:
                existing = json.load(fh)
        if args.smoke and existing.get("rows"):
            # CI smoke must not clobber the recorded full-scale rows
            existing["smoke"] = {k: out[k] for k in ("rows", "churn")}
            out = existing
        else:
            fresh = {r["n"] for r in out["rows"]}
            out["rows"] += [r for r in existing.get("rows", [])
                            if r["n"] not in fresh]
            out["rows"].sort(key=lambda r: r["n"])
            if "smoke" in existing:
                out["smoke"] = existing["smoke"]
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"wrote {path}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(0 if main() else 1)
