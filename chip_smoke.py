#!/usr/bin/env python3
"""Smoke run of the DR-FL main path on a TPU.

    python chip_smoke.py             # one chip: phases a-c
    python chip_smoke.py --chips 4   # four chips: the sharded fleet step

One chip:
  a. The Pallas kernels against their references at the shapes the main
     path uses (layer_agg at the paper-width ResNet-18 stack, rmsnorm and
     flash_attention at the transformer's and the set mixer's shapes).
  b. The paper's deployment at full ResNet-18 width through
     ``run_simulation``: sync MARL over 64 devices, so the bucketed client
     executor and the stacked ``layer_agg`` aggregation run.
  c. The transformer family at its own width, async, 64 devices.
Four chips (``--chips 4``), and nothing else:
  ``dual_selection_energy_step`` on a 2^20-device FleetState sharded over a
  4-chip fleet mesh, against the same step on one device.

Each phase is a function with size arguments, so the tests can run it at
tiny sizes on the CPU.  ``main`` requires a TPU: without one it exits
non-zero and prints no result.  The last line of stdout is one JSON object
naming the device.  Times printed are smoke timings (XLA compile seconds
apart from the rest), not speeds.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: max |kernel - reference| allowed in phase a, set from the dtype (f32)
#: and the reduction lengths, before any chip run: layer_agg and rmsnorm are
#: f32 VPU sums over <= 1,024 terms; flash attention adds two MXU matmuls
TOL = {"layer_agg": 1e-4, "rmsnorm": 1e-4, "flash_attention": 1e-3}


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


class _CompileClock:
    """Wall seconds of a block, and the XLA compile seconds inside it."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax
        self.compile_s = 0.0
        self._t0 = time.perf_counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.compile_s += duration

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        self.wall_s = time.perf_counter() - self._t0

    def line(self):
        return (f"xla_compile_s={self.compile_s:.1f} "
                f"rest_s={self.wall_s - self.compile_s:.1f} "
                "(smoke timings, not speeds)")


class _LastCall:
    """Stands in for a jitted module function during a run, keeping the
    arguments of its last call (to compile and inspect that program
    afterwards) and what ``keep`` takes from each output."""

    def __init__(self, fn, keep=None):
        self.fn, self.keep = fn, keep
        self.args, self.kept = None, []

    def __call__(self, *args, **kwargs):
        out = self.fn(*args, **kwargs)
        self.args = (args, kwargs)
        if self.keep is not None:
            self.kept.append(self.keep(out))
        return out

    def compiled_text(self):
        _check(self.args is not None, f"{self.fn} was never called")
        args, kwargs = self.args
        return self.fn.lower(*args, **kwargs).compile().as_text()


@contextlib.contextmanager
def _recording(module, name, keep=None):
    rec = _LastCall(getattr(module, name), keep)
    setattr(module, name, rec)
    try:
        yield rec
    finally:
        setattr(module, name, rec.fn)


def _has_kernel(hlo_text, name):
    return "tpu_custom_call" in hlo_text and name in hlo_text


# ---------------------------------------------------------------------------
# a. kernels against their references
# ---------------------------------------------------------------------------


def _kernel_case(phase, name, op, ref, args, on_tpu):
    import jax
    import numpy as np
    fn = jax.jit(op)
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    run_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(*args)
    err = float(np.max(np.abs(np.asarray(out, np.float32)
                              - np.asarray(want, np.float32))))
    shapes = " ".join(str(list(a.shape)) for a in args)
    _say(phase, f"{name} {shapes}: max_abs_err={err:.3e} tol={TOL[name]:g} "
                f"compile_s={compile_s:.2f} run_s={run_s:.4f} "
                "(smoke timings, not speeds)")
    _check(math.isfinite(err) and err <= TOL[name],
           f"{name}: max_abs_err {err} over tol {TOL[name]}")
    if on_tpu:
        _check(_has_kernel(compiled.as_text(), name),
               f"{name}: no tpu_custom_call in the compiled program")
    return err


def phase_kernels(*, n_clients=32, rows=11084, seg=1024,
                  transformer=(32, 32, 4, 32), mixer=(16, 4, 65536, 32),
                  on_tpu=True, seed=0):
    """Kernels vs references.  ``rows`` x ``seg``: the paper-width ResNet-18
    stack template; ``transformer``: (batch, seq, heads, head_dim) at
    width_mult=1.0, hw=32; ``mixer``: (batch, seed queries, agents, dim)
    of the QMIX set mixer.  Returns {case: max_abs_err}."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregation import _stacked_mean_ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ops import attention_ref_bshd
    from repro.kernels.layer_agg import layer_agg_op
    from repro.kernels.rmsnorm import rmsnorm_op, rmsnorm_ref

    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    errs = {}
    U = jax.random.normal(k[0], (n_clients, rows, seg), jnp.float32)
    M = (jax.random.uniform(k[1], (n_clients, rows)) > 0.3
         ).astype(jnp.float32).at[:, 0].set(0.0)     # a row nobody holds
    w = jax.random.uniform(k[2], (n_clients,)) * 100.0 + 1.0
    errs["layer_agg"] = _kernel_case("a", "layer_agg", layer_agg_op,
                                     _stacked_mean_ref, (U, M, w), on_tpu)
    del U

    B, S, H, D = transformer
    d_model = H * D
    x = jax.random.normal(k[3], (B * S, d_model), jnp.float32) * 3.0
    scale = jax.random.normal(k[4], (d_model,), jnp.float32)
    errs["rmsnorm"] = _kernel_case("a", "rmsnorm", rmsnorm_op, rmsnorm_ref,
                                   (x, scale), on_tpu)

    qkv = jax.random.normal(k[5], (3, B, S, H, D), jnp.float32)
    errs["flash_attention/transformer"] = _kernel_case(
        "a", "flash_attention",
        functools.partial(flash_attention, causal=True, block_q=S,
                          block_k=S),
        functools.partial(attention_ref_bshd, causal=True),
        tuple(qkv), on_tpu)

    Bm, Sq, N, Dm = mixer
    q = jax.random.normal(k[6], (Bm, Sq, 1, Dm), jnp.float32)
    kv = jax.random.normal(k[7], (2, Bm, N, 1, Dm), jnp.float32)
    errs["flash_attention/set_mixer"] = _kernel_case(
        "a", "flash_attention",
        functools.partial(flash_attention, causal=False),
        functools.partial(attention_ref_bshd, causal=False),
        (q, kv[0], kv[1]), on_tpu)
    return errs


# ---------------------------------------------------------------------------
# b./c. rounds through run_simulation
# ---------------------------------------------------------------------------


def _dispatch(cfg, agg_hlo):
    """The branch taken at each backend-keyed dispatch of the round path."""
    from repro.fl import batch as fl_batch
    from repro.fl.engine import resolve_client_executor
    from repro.models import transformer_family
    from repro.models.family import get_family
    ctx = get_family(cfg.model_family).bucket_trace_context()
    report = {
        "fl/engine.py client_executor": resolve_client_executor(cfg),
        "fl/batch.py scan_unroll": fl_batch._scan_unroll(),
        "family bucket_trace_context": type(ctx).__name__,
    }
    if cfg.model_family == "transformer":
        report["models/transformer_family.py pallas"] = \
            transformer_family._use_pallas()
    if agg_hlo is not None:
        report["core/aggregation.py layer_agg kernel"] = _has_kernel(
            agg_hlo, "layer_agg")
    return report


_TPU_BRANCH = {
    "fl/engine.py client_executor": "batched",
    "fl/batch.py scan_unroll": 1,
    "family bucket_trace_context": "nullcontext",
    "models/transformer_family.py pallas": True,
    "core/aggregation.py layer_agg kernel": True,
}


def _run_rounds(phase, cfg, on_tpu):
    """One run_simulation with the aggregation and bucket programs
    recorded; checks what every round path must show."""
    import jax
    import numpy as np

    from repro.core.fleet import fleet_total_remaining
    from repro.fl import batch as fl_batch
    from repro.fl import server as fl_server
    from repro.fl import run_simulation
    from repro.fl.engine import build_world

    world = build_world(cfg)
    e0 = fleet_total_remaining(world.fleet)
    p0 = world.global_params
    with _recording(fl_server, "_stacked_agg_program") as agg, \
            _recording(fl_batch, "_bucket_program",
                       keep=lambda out: np.asarray(out[1])) as bucket, \
            _CompileClock() as clock:
        hist = run_simulation(cfg)
        jax.block_until_ready(hist["params"])
    _say(phase, f"{cfg.model_family} width_mult={cfg.width_mult} "
                f"hw={cfg.hw} n={cfg.n_devices} {cfg.engine_mode}: "
                f"{len(hist['acc_mean'])} rounds, {clock.line()}")
    losses = np.concatenate(bucket.kept) if bucket.kept else np.zeros(0)
    energy = [float(e) for e in hist["energy"]]
    _say(phase, f"acc_mean={[round(a, 4) for a in hist['acc_mean']]} "
                f"client_loss_mean={float(np.mean(losses)):.4f} "
                f"bucket_programs={len(bucket.kept)} "
                f"energy_J={e0:.1f}->{energy[-1]:.1f}")
    _check(len(hist["acc_mean"]) == cfg.n_rounds,
           f"{len(hist['acc_mean'])} of {cfg.n_rounds} rounds finished")
    _check(len(losses) > 0, "the bucketed executor never ran")
    _check(np.isfinite(losses).all(), "non-finite client loss")
    _check(np.isfinite(hist["acc_mean"]).all(), "non-finite accuracy")
    changed = any(not np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree.leaves(p0),
                                  jax.tree.leaves(hist["params"])))
    _check(changed, "the global parameters did not change")
    _check(all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(hist["params"])),
           "non-finite global parameters")
    _check(energy[-1] < e0, f"fleet energy did not fall ({e0} -> "
                            f"{energy[-1]})")
    report = _dispatch(cfg, agg.compiled_text() if on_tpu else None)
    _say(phase, f"dispatch: {report}")
    if on_tpu:
        for key, got in report.items():
            _check(got == _TPU_BRANCH[key],
                   f"{key}: took {got!r}, the TPU branch is "
                   f"{_TPU_BRANCH[key]!r}")
    return hist, bucket


def _config(family, mode, *, n_devices, width_mult, hw, n_train, rounds,
            local_epochs, seed):
    """MARL selection, 10% participation, batch 32 (paper section 5)."""
    from repro.fl import FLConfig
    return FLConfig(n_devices=n_devices, n_rounds=rounds, participation=0.1,
                    local_epochs=local_epochs, batch_size=32,
                    n_train=n_train, hw=hw, width_mult=width_mult,
                    model_family=family, selector="marl", engine_mode=mode,
                    seed=seed)


def phase_cnn(*, n_devices=64, width_mult=1.0, hw=32, n_train=12800,
              rounds=2, local_epochs=5, on_tpu=True, seed=0):
    """The paper's deployment: ResNet-18 at ``width_mult`` (1.0 = the
    published widths), sync."""
    cfg = _config("cnn", "sync", n_devices=n_devices, width_mult=width_mult,
                  hw=hw, n_train=n_train, rounds=rounds,
                  local_epochs=local_epochs, seed=seed)
    hist, _ = _run_rounds("b", cfg, on_tpu)
    return hist


def phase_transformer(*, n_devices=64, width_mult=1.0, hw=32, n_train=6400,
                      rounds=2, local_epochs=5, on_tpu=True, seed=0):
    """The early-exit transformer at ``width_mult``, async; on TPU its
    step program must hold both Pallas kernels."""
    cfg = _config("transformer", "async", n_devices=n_devices,
                  width_mult=width_mult, hw=hw, n_train=n_train,
                  rounds=rounds, local_epochs=local_epochs, seed=seed)
    hist, bucket = _run_rounds("c", cfg, on_tpu)
    if on_tpu:
        step_text = bucket.compiled_text()
        for name in ("rmsnorm", "flash_attention"):
            _check(_has_kernel(step_text, name),
                   f"the transformer step program holds no {name} kernel")
        _say("c", "step program holds tpu_custom_call for rmsnorm and "
                  "flash_attention")
    return hist


# ---------------------------------------------------------------------------
# four chips: the sharded fleet step
# ---------------------------------------------------------------------------


def phase_fleet_sharded(*, n=2 ** 20, n_chips=4, seed=0):
    """``dual_selection_energy_step`` on an ``n``-device FleetState
    row-sharded over an ``n_chips`` fleet mesh vs the same step on one
    device: equal results, and every [n] field split ``n_chips`` ways."""
    import jax
    import numpy as np

    from repro.core.fleet import _ARRAY_FIELDS, sample_fleet_state
    from repro.core.marl.networks import agent_hidden_init, agent_init
    from repro.core.selection import OBS_DIM, dual_selection_energy_step_jit
    from repro.sharding.fleet import (fleet_mesh, shard_agent_array,
                                      shard_fleet)

    sizes = (2.8e6, 8.4e6, 22.5e6, 44.8e6)
    fracs = (0.11, 0.3, 0.72, 1.0)
    k = max(1, n // 10)
    params = agent_init(jax.random.PRNGKey(seed), OBS_DIM, len(sizes) + 1)
    single = sample_fleet_state(n, seed=seed, backend="jax")
    hidden = agent_hidden_init(n)
    mesh = fleet_mesh(n_chips)
    sharded = shard_fleet(single, mesh)
    hidden_s = shard_agent_array(hidden, mesh)

    def split_ways(fleet):
        bad = []
        for f in _ARRAY_FIELDS:
            arr = getattr(fleet, f)
            rows = sorted(s.data.shape[0] for s in arr.addressable_shards)
            devs = {s.device for s in arr.addressable_shards}
            if len(devs) != n_chips or rows != [n // n_chips] * n_chips:
                bad.append(f"{f}: {rows}")
        return bad

    bad = split_ways(sharded)
    _check(not bad, f"fleet fields not split {n_chips} ways: {bad}")

    def step(fleet, h):
        return dual_selection_energy_step_jit(params, h, fleet, sizes, fracs,
                                              k=k, n_rounds=100)

    out = {}
    for name, fleet, h in (("single", single, hidden),
                           ("sharded", sharded, hidden_s)):
        with _CompileClock() as clock:
            res = jax.block_until_ready(step(fleet, h))
        out[name] = res
        n_dev = len(fleet.remaining.sharding.device_set)
        _say("fleet", f"{name}: n={n} devices={n_dev} {clock.line()}")
    (f1, h1, p1, a1, s1), (f2, h2, p2, a2, s2) = out["single"], out["sharded"]
    bad = split_ways(f2)
    _check(not bad, f"stepped fleet not split {n_chips} ways: {bad}")
    _check(np.array_equal(np.asarray(a1), np.asarray(a2)), "actions differ")
    _check(np.array_equal(np.asarray(p1), np.asarray(p2)),
           "participants differ")
    for f in _ARRAY_FIELDS:
        _check(np.array_equal(np.asarray(getattr(f1, f)),
                              np.asarray(getattr(f2, f))),
               f"fleet field {f} differs")
    _check(np.allclose(np.asarray(h1), np.asarray(h2), rtol=1e-6, atol=1e-6),
           "agent hidden states differ")
    # the summary ends in cross-shard sums, reduced in another order
    _check(np.allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-6),
           "fleet summaries differ")
    _say("fleet", f"sharded == single: participants={int(np.sum(p1))} "
                  f"fields split {n_chips} ways x {n // n_chips} rows")
    return out


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ is missing ({e})",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 3
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    phases = ([functools.partial(phase_fleet_sharded, n_chips=4)]
              if args.chips == 4
              else [phase_kernels, phase_cnn, phase_transformer])
    failed = []
    for phase in phases:
        # every phase runs even after one fails: a chip call is costly,
        # so one call reports all of them
        try:
            phase()
        except Exception:  # noqa: BLE001 - reported, then the run fails
            traceback.print_exc()
            failed.append(getattr(phase, "func", phase).__name__)
    if failed:
        print(f"chip_smoke: FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
