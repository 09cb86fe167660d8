"""Drives one cell's rounds through the program and records what the
metrics and the check read.

The entry the window drives is ``repro.fl.run_simulation`` and, under it,
``RoundEngine.run``.  Nothing in ``src/`` is edited: the harness wraps
module functions from outside for the length of a run, and ends a pass by
raising :class:`Stop` from the selector's ``select``, the first call of
every (virtual) round.

A run makes two passes over the same episode, from the same seed:

* warm-up: untimed rounds, until the rounds' own time (compilation
  subtracted) reaches :data:`WARMUP_FACTOR` times the window, so that
  every program shape the window will meet has been compiled or loaded;
* window: the same episode again from its first round, timed from the
  start of round 0 to the first round boundary at or past ``seconds``.

The deployment (the data, its Dirichlet shards, the device profiles and
the selector's own random streams) comes from the traffic's
``deployment_seed``; the run's seed gives the model's weights, the
clients' batch orders and the replay sampling.  So every seed meets the
same fleet and shard sizes, and the episode is deterministic in the two
seeds: the window replays exactly the shapes (submodel, clients, steps)
the warm-up compiled.  Its first
:data:`CHECK_ROUNDS` rounds are recorded for the comparison with the plain
reference.  With ``trace=True`` the window instead profiles
:data:`TRACE_ROUNDS` rounds after its first.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import shutil
import time
from typing import Dict, List, Optional

import jax
import numpy as np

#: JAX's duration events that are compilation work, not round work
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
#: the compile cache's miss event: set-up that records one compiled afresh
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
#: the warm-up runs until its rounds' own time reaches this many windows
WARMUP_FACTOR = 1.25
#: rounds at the window's start that are compared with the plain reference
CHECK_ROUNDS = 2
#: rounds a traced run profiles, after the window's first
TRACE_ROUNDS = 2
SPAN_PREFIX = "bench:"
TRACED_SPAN = SPAN_PREFIX + "traced"


class Stop(Exception):
    """Ends a pass at a round boundary."""


class EpisodeEnded(RuntimeError):
    """The episode ran out (dead fleet, spent budget, last round) before
    the pass it was in was over."""


class CompileClock:
    """Counts compilations and their seconds, process-wide."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_misses = 0
        self.traces = 0
        self.names = collections.Counter()   # traced or compiled functions
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1

    def _on(self, event, duration, fun_name=None, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == COMPILE_EVENTS[2]:
            self.backend_compiles += 1
            self.names[f"compile:{fun_name}"] += 1
        elif event == COMPILE_EVENTS[0]:
            self.traces += 1
            self.names[f"trace:{fun_name}"] += 1

    def snapshot(self):
        return (self.backend_compiles, self.traces, self.seconds)


_CLOCK: Optional[CompileClock] = None


def compile_clock() -> CompileClock:
    """The process's one clock (a listener, once registered, stays)."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


@dataclasses.dataclass
class BucketCall:
    round: int
    model_idx: int
    p_pad: int
    t_pad: int
    batch: int
    real_steps: int
    real_samples: List[int]          # per real client: steps * batch


@dataclasses.dataclass
class AggCall:
    round: int
    n_rows: int                      # stacked client rows, padding included
    n_real: int                      # rows of real clients


@dataclasses.dataclass
class CheckRound:
    participants: List[int] = dataclasses.field(default_factory=list)
    model_choice: List[int] = dataclasses.field(default_factory=list)
    k: int = 0
    losses: Dict[int, float] = dataclasses.field(default_factory=dict)
    params: object = None
    accs: Optional[np.ndarray] = None
    charge: object = None            # (remaining after, ok), on device


@dataclasses.dataclass
class Record:
    """What a run's window produced, for the metrics and the check."""
    rounds: int = 0
    window_s: float = 0.0
    t_open: float = 0.0
    setup_compile_s: float = 0.0      # compiling and cache loads in set-up
    setup_cache_misses: int = 0       # programs set-up compiled afresh
    compiles_in_window: int = 0
    traces_in_window: int = 0
    compiled_in_window: List[str] = dataclasses.field(default_factory=list)
    buckets: List[BucketCall] = dataclasses.field(default_factory=list)
    aggs: List[AggCall] = dataclasses.field(default_factory=list)
    evals: Dict[int, int] = dataclasses.field(default_factory=dict)
    params0: object = None
    accs0: Optional[np.ndarray] = None
    check: List[CheckRound] = dataclasses.field(default_factory=list)
    trace_file: Optional[str] = None
    traced_rounds: List[int] = dataclasses.field(default_factory=list)
    warmup_rounds: int = 0
    capped: bool = False              # the window stopped at the warm-up's end


class _Pass:
    """Round-boundary state of one pass."""

    def __init__(self, kind):
        self.kind = kind
        self.last_round = None
        self.t0 = self.c0 = None


class Runner:
    def __init__(self, cfg, *, seconds, deployment_seed, trace_dir=None,
                 warmup=True):
        self.cfg = cfg
        self.deployment = dataclasses.replace(cfg, seed=int(deployment_seed))
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.warmup = warmup
        self.clock = compile_clock()
        self.rec = Record()
        self.world = None
        self.round = -1
        self.phase = None
        self._pass = None
        self._traced = None
        self._snap = None
        self._learner = None

    # -- round boundaries ---------------------------------------------------

    def _boundary(self, selector, round_idx):
        p = self._pass
        if round_idx == p.last_round:
            return
        p.last_round = round_idx
        learner = getattr(selector, "learner", None)
        if learner is not None:
            # the previous round's QMIX update is the last device work
            # queued before this boundary
            jax.block_until_ready(learner.params)
        now = time.perf_counter()
        self.round = round_idx
        if p.t0 is None:
            p.t0, p.c0 = now, self.clock.seconds
            if p.kind == "window":
                self.rec.t_open = now
                self._snap = self.clock.snapshot()
                self.rec.setup_compile_s = self._snap[2]
                self.rec.setup_cache_misses = self.clock.cache_misses
                self.clock.names.clear()
            return
        if p.kind == "warmup":
            self.rec.warmup_rounds = round_idx
            busy = (now - p.t0) - (self.clock.seconds - p.c0)
            if self.trace_dir is not None:
                done = round_idx >= TRACE_ROUNDS + 1
            else:
                done = (round_idx >= CHECK_ROUNDS
                        and busy >= WARMUP_FACTOR * self.seconds)
            if done:
                raise Stop()
            return
        if self.trace_dir is not None:
            if round_idx == 1:
                self._start_trace()
            elif round_idx == 1 + TRACE_ROUNDS:
                self._stop_trace()
                self._close(now, round_idx)
            return
        if round_idx >= CHECK_ROUNDS and now - p.t0 >= self.seconds:
            self._close(now, round_idx)
        if self.warmup and round_idx >= self.rec.warmup_rounds:
            # never run a round the warm-up did not: its shapes may be new
            self.rec.capped = True
            self._close(now, round_idx)

    def _close(self, now, round_idx):
        self.rec.rounds = round_idx
        self.rec.window_s = now - self._pass.t0
        b, t, _ = self.clock.snapshot()
        self.rec.compiles_in_window = b - self._snap[0]
        self.rec.traces_in_window = t - self._snap[1]
        names = self.clock.names.most_common()
        self.rec.compiled_in_window = (
            [f"{k} x{v}" for k, v in names if k.startswith("compile:")]
            + [f"{k} x{v}" for k, v in names[:6] if k.startswith("trace:")])
        raise Stop()

    def _start_trace(self):
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._traced = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._traced.__enter__()
        self.rec.traced_rounds = list(range(1, 1 + TRACE_ROUNDS))

    def _stop_trace(self):
        self._traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = []
        for root, _, files in os.walk(self.trace_dir):
            found += [os.path.join(root, f) for f in files
                      if f.endswith(".xplane.pb")]
        self.rec.trace_file = sorted(found)[-1] if found else None

    # -- wrappers -------------------------------------------------------------

    def _recording(self):
        return self.phase == "window"

    def _check_round(self) -> Optional[CheckRound]:
        if self._recording() and 0 <= self.round < CHECK_ROUNDS:
            while len(self.rec.check) <= self.round:
                self.rec.check.append(CheckRound())
            return self.rec.check[self.round]
        return None

    def _span(self, name, fn):
        label = SPAN_PREFIX + name

        def wrapped(*args, **kwargs):
            with jax.profiler.TraceAnnotation(label):
                return fn(*args, **kwargs)
        return wrapped

    #: jitted callables a QMIX learner builds per instance
    LEARNER_PROGRAMS = ("_act", "_update")

    def _share_learner_programs(self, sel):
        """``QmixLearner`` jits ``functools.partial(fn, cfg)`` afresh in
        every instance, so the window pass's new selector would trace and
        compile its programs again inside the window.  Its programs are
        handed the warm-up learner's, which compute the same function of
        the same config."""
        learner = getattr(sel, "learner", None)
        if learner is None:
            return
        first = self._learner
        if first is None:
            self._learner = learner
        elif first.cfg == learner.cfg:
            for name in self.LEARNER_PROGRAMS:
                if hasattr(first, name):
                    setattr(learner, name, getattr(first, name))

    def _wrap_make_selector(self, orig):
        drv = self

        def make(cfg, n_models):
            sel = orig(drv.deployment, n_models)
            drv._share_learner_programs(sel)
            inner = sel.select

            def select(fleet, round_idx, k, *args, **kwargs):
                drv._boundary(sel, round_idx)
                with jax.profiler.TraceAnnotation(SPAN_PREFIX + "select"):
                    out = inner(fleet, round_idx, k, *args, **kwargs)
                cr = drv._check_round()
                if cr is not None and not cr.participants:
                    cr.participants = [int(i) for i in out.participants]
                    cr.model_choice = [int(m) for m in out.model_choice]
                    cr.k = int(k)
                return out
            sel.select = select
            return sel
        return make

    def _wrap_build_world(self, orig):
        def build(cfg):
            if self.world is None:
                world = orig(self.deployment)
                world.global_params = world.family.init(
                    jax.random.PRNGKey(cfg.seed), cfg.num_classes,
                    width_mult=cfg.width_mult, hw=cfg.hw)
                self.world = world
            return self.world
        return build

    def _wrap_run_bucket(self, orig):
        cfg = self.cfg

        def run_bucket(method, global_params, x_all, y_all, bucket, **kw):
            if self._recording():
                p_pad, t_pad, batch = bucket.gather.shape
                per = [cfg.local_epochs * max(1, int(w) // batch)
                       for w in bucket.weights]
                self.rec.buckets.append(BucketCall(
                    round=self.round, model_idx=int(bucket.model_idx),
                    p_pad=int(p_pad), t_pad=int(t_pad), batch=int(batch),
                    real_steps=int(sum(per)),
                    real_samples=[s * int(batch) for s in per]))
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + "bucket_program"):
                return orig(method, global_params, x_all, y_all, bucket, **kw)
        return run_bucket

    def _wrap_run_cohort(self, orig):
        def run_cohort(method, global_params, *args, **kwargs):
            res = orig(method, global_params, *args, **kwargs)
            cr = self._check_round()
            if cr is not None:
                for b in res.buckets:
                    for r, i in enumerate(b.participants):
                        cr.losses[int(i)] = float(b.losses[r])
            return res
        return run_cohort

    def _wrap_aggregate(self, orig):
        def aggregate(global_params, buckets, *args, **kwargs):
            buckets = list(buckets)
            if self._recording():
                ws = [w for b in buckets for w in b[2]]
                self.rec.aggs.append(AggCall(
                    round=self.round, n_rows=len(ws),
                    n_real=sum(1 for w in ws if w > 0)))
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + "aggregation"):
                out = orig(global_params, buckets, *args, **kwargs)
            cr = self._check_round()
            if cr is not None:
                cr.params = out[0] if isinstance(out, tuple) else out
            return out
        return aggregate

    def _wrap_evaluate(self, orig):
        def evaluate(params, *args, **kwargs):
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + "eval"):
                accs = orig(params, *args, **kwargs)
            if self._recording():
                self.rec.evals[self.round] = self.rec.evals.get(
                    self.round, 0) + 1
                if self.round < 0 and self.rec.accs0 is None:
                    self.rec.accs0 = np.asarray(accs)
                cr = self._check_round()
                if cr is not None:
                    cr.accs = np.asarray(accs)
            return accs
        return evaluate

    def _wrap_charge(self, orig):
        def charge(fleet, need, active):
            out = orig(fleet, need, active)
            cr = self._check_round()
            if cr is not None and cr.charge is None:
                cr.charge = (out[0].remaining, out[1])
            return out
        return charge

    @contextlib.contextmanager
    def installed(self):
        from repro.fl import batch as fl_batch
        from repro.fl import engine, server, simulation
        plan = [
            (simulation, "_make_selector", self._wrap_make_selector),
            (engine, "build_world", self._wrap_build_world),
            (fl_batch, "run_bucket", self._wrap_run_bucket),
            (fl_batch, "run_cohort", self._wrap_run_cohort),
            (fl_batch, "bucket_cohort",
             lambda f: self._span("bucket_schedule", f)),
            (server, "aggregate_drfl_stacked", self._wrap_aggregate),
            (server, "evaluate", self._wrap_evaluate),
            (engine, "_marl_train", lambda f: self._span("qmix", f)),
            (engine, "fleet_charge_jit", self._wrap_charge),
        ]
        saved = []
        try:
            for mod, name, wrap in plan:
                orig = getattr(mod, name)
                saved.append((mod, name, orig))
                setattr(mod, name, wrap(orig))
            yield self
        finally:
            for mod, name, orig in reversed(saved):
                setattr(mod, name, orig)

    # -- passes ---------------------------------------------------------------

    def _one_pass(self, kind):
        from repro.fl import run_simulation
        self._pass = _Pass(kind)
        self.phase = kind
        self.round = -1
        try:
            hist = run_simulation(self.cfg)
        except Stop:
            return
        finally:
            self.phase = None
        end = hist.get("terminated", {})
        raise EpisodeEnded(
            f"the episode ended in the {kind} pass after "
            f"{len(hist.get('acc_mean', []))} rounds "
            f"({end.get('reason', 'no reason given')}); size n_rounds and "
            "the budgets so that it outlasts warm-up and window")

    def run(self) -> Record:
        """Warm-up pass (unless disabled), then the window pass."""
        with self.installed():
            if self.warmup:
                self._one_pass("warmup")
            self.rec.params0 = self.world.global_params if self.world else None
            self._one_pass("window")
            if self.rec.params0 is None:
                self.rec.params0 = self.world.global_params
        if not self.rec.buckets:
            raise RuntimeError(
                "the window ran no bucket program: the engine took its "
                "per-client executor, which the benchmark does not measure")
        return self.rec

    def release(self):
        """Drop the program's state so the reference has the device."""
        self.world = None
