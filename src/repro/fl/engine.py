"""Event-driven FL round engine: sync (barrier) and async (timeline) modes.

The paper's §4.2 workflow assumes devices come and go on their own clocks,
but a synchronous round loop is a barrier: every round waits ``max(t_cost)``
over its participants, so one slow straggler sets the fleet's wall-clock —
the "wooden barrel effect" DR-FL is supposed to beat.  This module replaces
the monolithic loop with a scheduler over *events* on a simulated timeline:

* ``mode="sync"``  — one DISPATCH + one barrier COMPLETION per round; a
  verbatim port of the legacy loop, bit-for-bit identical to the frozen
  reference (:func:`repro.fl.simulation._run_once_reference`, enforced by
  ``tests/test_engine.py``).
* ``mode="async"`` — dispatch (selection + energy charge at send time) and
  completion (delta arrival + staleness-aware aggregation at finish time)
  are separate events on a heap keyed by per-device virtual clocks
  (``FleetState.busy_until``).  The server keeps ~k tasks in flight: each
  completion aggregates immediately (FedAsync-style, down-weighted by
  :func:`repro.fl.server.staleness_scale`) and back-fills the freed slot,
  so no device ever waits at a barrier.  Hot-plug joins, dropouts, and
  battery depletion are timeline events, not round-boundary hacks.

Async bookkeeping groups completions into *virtual rounds* of k tasks so
histories stay row-comparable with sync runs; rewards are credited at
EVENT time (energy at dispatch, duration and accuracy-delta at arrival)
and committed to the selector in dispatch order, which keeps the MARL
episode trace (obs/action/reward) aligned.

Fairness accounting reported in the history (``benchmarks/async_bench.py``):

* ``idle_time`` — straggler wait: how long each finished client update sat
  before entering the global model.  Sync pays ``t_round - t_cost_i`` per
  surviving participant (the barrier); async aggregates at the completion
  event, so the wait is zero by construction (computed, not assumed, so
  the metric stays honest if scheduling ever batches arrivals).
* ``wait_for_work`` (async only) — time between a device completing a task
  and its NEXT dispatch; spare capacity, the analogue of sync devices
  sitting out a round, reported for scheduling diagnostics.

Public surface (one-line contracts):

* :class:`RoundEngine` — runs one FL episode under ``cfg.engine_mode``;
  ``run()`` returns the history dict (selector/buffer owned by caller).
* :class:`World` — per-episode immutable setup bundle (data shards,
  fleet, global model, family, paper-scale cost calibration).
* :func:`build_world` — build a :class:`World` from a config; shards the
  fleet over the ``"fleet"`` mesh when ``cfg.fleet_mesh`` asks for it.
* :func:`resolve_client_executor` — map ``cfg.client_executor`` ("auto" /
  "perclient" / "batched") to the concrete executor for this backend.
* :func:`sync_task_budget` — total client tasks a sync run dispatches at
  most (the async engine's default work budget).
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint.engine import (CheckpointHalt, EngineCheckpointer,
                                     config_fingerprint)
from repro.checkpoint.io import FLEET_CHECKPOINT_FIELDS
from repro.core.fleet import (FleetState, fleet_charge_jit, fleet_connect,
                              fleet_cost_matrix_jit, fleet_disconnect,
                              fleet_is_jax, fleet_kill, fleet_set_alive,
                              fleet_set_busy, fleet_total_remaining,
                              make_fleet_state)
from repro.core.selection import MarlSelector
from repro.data.partition import dirichlet_partition
from repro.energy import EnergyScenario, scenario_from_config
from repro.fl import batch as fl_batch
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.fl.faults import FaultPlan, poison_payload
from repro.models.family import ModelFamily, get_family


class _RestoredBucket(NamedTuple):
    """Stand-in for a BucketResult restored from a checkpoint: the task's
    own row was sliced to ``[1, ...]`` at save time, so row index 0 of this
    bucket reproduces the original ``bucket.stacked_delta[row:row+1]``
    slice bit-for-bit."""
    stacked_delta: Any


# ---------------------------------------------------------------------------
# shared episode setup (data shards, fleet, global model, cost calibration)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class World:
    """Everything one simulation episode needs, built once per episode."""
    x_tr: np.ndarray
    y_tr: np.ndarray
    x_val: np.ndarray
    y_val: np.ndarray
    parts: List[np.ndarray]
    fleet: FleetState
    global_params: Any
    n_models: int
    sizes: tuple
    fractions: tuple
    n_total: int
    family: ModelFamily = None
    scenario: EnergyScenario = None


def _validate_energy_feasibility(cfg, fleet, sizes, fractions) -> None:
    """Fail fast on budgets no fresh device can survive.

    ``fleet_charge`` uses a strict ``remaining > need`` survival check: a
    device whose FULL battery (``battery * energy_scale``) cannot cover even
    its cheapest submodel dies the first time any selector picks it — at
    small scales that silently wipes the whole fleet in round 0.  Surface
    the misconfiguration at build time instead, naming the offending
    devices and their cheapest submodel."""
    from repro.core.fleet import fleet_cost_matrix
    _, _, e_tra, e_com = fleet_cost_matrix(fleet, sizes, fractions,
                                           cfg.local_epochs, cfg.batch_size)
    # jaxlint: allow(host-sync-in-hot-path) -- one-time build_world validation pull, before any round runs
    need, battery = jax.device_get((e_tra + e_com, fleet.battery))
    need = np.asarray(need, np.float64)
    fresh = np.asarray(battery, np.float64) * float(cfg.energy_scale)
    min_need = need.min(axis=1)
    bad = np.flatnonzero(min_need >= fresh)
    if bad.size:
        cheapest = need.argmin(axis=1)
        detail = "; ".join(
            f"device {int(i)}: cheapest submodel {int(cheapest[i])} needs "
            f"{min_need[i]:.1f}J >= fresh battery {fresh[i]:.1f}J"
            for i in bad[:5])
        more = f" (+{bad.size - 5} more)" if bad.size > 5 else ""
        raise ValueError(
            f"energy.scale={cfg.energy_scale} leaves {bad.size}/{len(fresh)}"
            " device(s) unable to afford even their cheapest submodel on a "
            "FULL battery — they die the first round any selector picks "
            f"them (fleet_charge survival is strict '>'): {detail}{more}. "
            "Raise energy.scale, or lower local_epochs/model cost.")


def build_world(cfg) -> World:
    """Exact port of the legacy ``_run_once`` setup (shared by the engine
    and the frozen reference loop, so parity starts from identical state)."""
    key = jax.random.PRNGKey(cfg.seed)
    family = get_family(getattr(cfg, "model_family", None))
    # family-routed corpus: image families keep the exact legacy
    # synthetic_image_dataset call (bit-for-bit), token families serve
    # [n, seq] context windows through the same (x, y) row contract
    x, y = family.make_dataset(cfg.n_train, cfg.num_classes, hw=cfg.hw,
                               noise=cfg.noise, seed=cfg.seed)
    n_val = max(64, int(cfg.n_val_fraction * cfg.n_train))
    x_val, y_val = x[:n_val], y[:n_val]          # server-side validation set
    x_tr, y_tr = x[n_val:], y[n_val:]
    parts = dirichlet_partition(y_tr, cfg.n_devices + cfg.hotplug_n,
                                cfg.alpha, cfg.seed)

    n_total = cfg.n_devices + cfg.hotplug_n
    fleet = make_fleet_state(n_total, cfg.seed,
                             data_sizes=[len(p) for p in parts],
                             backend="jax")
    fleet = fleet.replace(remaining=fleet.battery * cfg.energy_scale)
    scenario = scenario_from_config(cfg)
    if not scenario.is_trivial:
        # profile parameter arrays (harvest amplitude, timezone phase) are
        # drawn for the FULL fleet — hotplug joiners included — from a
        # dedicated RNG stream, so the default scenario keeps the fleet
        # bit-for-bit untouched
        fleet = scenario.init_fleet(fleet, cfg.seed)
    if cfg.hotplug_n:                   # hot-plug devices: not yet connected
        fleet = fleet_disconnect(fleet, cfg.n_devices)
    if getattr(cfg, "fleet_mesh", 0) not in (0, 1):
        # opt-in data-parallel placement: [n] arrays row-sharded over the
        # "fleet" mesh so the per-round kernels run SPMD (no-op when the
        # runtime has a single device)
        from repro.sharding.fleet import maybe_shard_fleet
        fleet = maybe_shard_fleet(fleet, cfg.fleet_mesh)
    global_params = family.init(key, cfg.num_classes,
                                width_mult=cfg.width_mult, hw=cfg.hw)
    M = family.num_submodels()
    # Energy/time accounting (Eq. 5 & 7) is calibrated to the PAPER-scale
    # backbone (full-width model on 32x32): the slim model is only the
    # CPU-budget compute proxy; batteries must see paper-scale costs for the
    # wooden-barrel dynamics to reproduce.
    sizes, fractions = family.cost_model(cfg.num_classes)
    _validate_energy_feasibility(cfg, fleet, sizes, fractions)
    return World(x_tr=x_tr, y_tr=y_tr, x_val=x_val, y_val=y_val, parts=parts,
                 fleet=fleet, global_params=global_params, n_models=M,
                 sizes=sizes, fractions=fractions, n_total=n_total,
                 family=family, scenario=scenario)


def _check_selection(sel, n_total: int) -> None:
    """The engine indexes ``model_choice`` by raw device id — a selector
    returning fewer entries than the fleet silently mis-indexes."""
    if len(sel.model_choice) != n_total:
        raise ValueError(
            f"selector returned {len(sel.model_choice)} model choices "
            f"for a fleet of {n_total}")


def _client_update(cfg, family, global_params, m, xi, yi, seed):
    return family.client_update(cfg.method, global_params, m, xi, yi,
                                epochs=cfg.local_epochs, batch=cfg.batch_size,
                                lr=cfg.lr, seed=seed)


# Above this per-step work, XLA CPU executes the per-client convs at
# BLAS-bound speed and batching them (vmapped GEMMs) cannot win — measured
# crossover between 1.8e7 (batched 2x faster) and 5.6e8 (batched 0.7x)
# FLOPs per training step on 2-core CPU; see benchmarks/client_bench.py.
_CPU_BATCHED_STEP_FLOPS = 5e7


def resolve_client_executor(cfg) -> str:
    """``cfg.client_executor``: "perclient" | "batched" | "auto".

    "auto" picks the bucketed-vmap executor (repro.fl.batch, <= 1 jit
    dispatch per submodel bucket per round) at 64+ device fleets — where
    per-participant dispatch dominates wall time — and the per-client path
    below that, which keeps small-fleet sync runs bit-for-bit equal to the
    frozen reference loop (vmap/scan fusion reorders float reductions at
    the ULP level, so the batched path is allclose, not bit-exact).  On
    CPU, large per-step models stay per-client: execution there is
    FLOP-bound, so bucketing only wins while per-op overhead dominates
    (small widths/images — exactly the CPU-budget large-fleet configs)."""
    mode = getattr(cfg, "client_executor", "auto")
    if mode == "auto":
        if cfg.n_devices < 64:
            return "perclient"
        if jax.default_backend() == "cpu":
            family = get_family(getattr(cfg, "model_family", None))
            step_flops = (family.flops_per_sample(
                family.num_submodels() - 1, cfg.hw, cfg.width_mult)
                * cfg.batch_size)
            return ("batched" if step_flops <= _CPU_BATCHED_STEP_FLOPS
                    else "perclient")
        return "batched"
    if mode in ("perclient", "batched"):
        return mode
    raise ValueError(f"unknown client_executor {mode!r} "
                     "(expected 'auto', 'perclient' or 'batched')")


def _run_batched_cohort(cfg, world, global_params, device_ids, model_idxs,
                        seeds, x_dev, y_dev,
                        fleet_steps) -> fl_batch.CohortResult:
    """One bucketed-vmap executor pass for ``device_ids`` (all must have
    local data).  Weights default to shard sizes inside run_cohort."""
    return fl_batch.run_cohort(
        cfg.method, global_params, x_dev, y_dev,
        [world.parts[i] for i in device_ids], device_ids, model_idxs, seeds,
        epochs=cfg.local_epochs, batch=cfg.batch_size, lr=cfg.lr,
        family=world.family, fleet_steps=fleet_steps)


def _resident_data(cfg, w):
    """The batched executor's device-resident training set and the
    fleet's longest local schedule, once per run."""
    return (fl_batch.resident(w.x_tr, w.family), jnp.asarray(w.y_tr),
            fl_batch.longest_schedule(w.parts, epochs=cfg.local_epochs,
                                      batch=cfg.batch_size))


def sync_task_budget(cfg) -> int:
    """Total client-task budget a sync run of ``cfg`` dispatches at most
    (sum over rounds of the connected-fleet Top-K k) — the async engine's
    default work budget, so both modes do the same amount of training."""
    k_pre = max(1, int(round(cfg.participation * cfg.n_devices)))
    if not cfg.hotplug_n:
        return cfg.n_rounds * k_pre
    hr = min(max(int(cfg.hotplug_round), 0), cfg.n_rounds)
    k_post = max(1, int(round(
        cfg.participation * (cfg.n_devices + cfg.hotplug_n))))
    return hr * k_pre + (cfg.n_rounds - hr) * k_post


def _marl_train(marl, buffer, hist, fleet, round_idx, n_updates):
    """Flush the episode trace into replay, run QMIX updates, and record
    effective-replay telemetry under ``hist["qmix"]`` (the resolved buffer
    capacity — possibly degraded by ``_make_buffer``'s obs budget — plus
    mixer mode, stored-agent width, update count and per-update TD loss),
    so fig5/table1 runs can report the replay the learner actually saw.

    Call order (episode_arrays → add_episode → sample/update loop) is
    byte-identical to the legacy inline blocks — the buffer RNG consumes
    the same draws, keeping sync parity with the frozen reference."""
    with telemetry.span("qmix"):
        obs, state, actions, rewards = marl.episode_arrays(fleet, round_idx)
        buffer.add_episode(obs, state, actions, rewards)
        losses = []
        for _ in range(n_updates):
            batch = buffer.sample(marl.learner.cfg.batch_size)
            if batch:
                losses.append(marl.learner.update(batch)["td_loss"])
    q = hist.setdefault("qmix", {
        "mixer_mode": marl.mixer_mode,
        "replay_capacity": buffer.capacity,
        "replay_episode_len": buffer.T,
        "replay_agents": buffer.N,
        "replay_episodes": 0,
        "updates": 0,
        "td_loss": [],
    })
    q["replay_episodes"] = len(buffer)
    q["updates"] = marl.learner.updates
    q["td_loss"].extend(losses)


def _upgrade_hist(hist: dict) -> dict:
    """A history saved by an older release, under today's keys: the
    straggler wait ``idle`` is ``straggler_wait_s``, and ``wall_clock``
    (host seconds per round) is no longer kept."""
    if "idle" in hist:
        hist["straggler_wait_s"] = hist.pop("idle")
    hist.pop("wall_clock", None)
    return hist


class RoundEngine:
    """Scheduler layer: runs one FL episode under ``cfg.engine_mode``.

    ``selector`` and (for MARL) ``buffer`` are owned by the caller —
    :func:`repro.fl.simulation.run_simulation` persists them across
    pre-training episodes exactly as the legacy loop did.

    Crash safety (opt-in, off by default so clean runs stay bit-for-bit):

    * ``cfg.checkpoint_dir`` + ``cfg.checkpoint_every`` — snapshot the FULL
      run state (fleet arrays, params, history, event heap, selector +
      replay buffer, partitions) every N rounds / virtual rounds via
      :class:`repro.checkpoint.engine.EngineCheckpointer`; pass the decoded
      state back as ``resume_state`` and the run continues byte-identically
      to one that was never interrupted.
    * ``fault_plan`` (or the ``cfg.fault_*`` counts) — seeded churn events
      injected into the async timeline; see :mod:`repro.fl.faults`.
    * ``halt_counter`` — ``{"remaining": N}`` shared dict: raise
      :class:`CheckpointHalt` right after the N-th checkpoint save (the
      test/bench hook that simulates a crash at a known point).
    """

    def __init__(self, cfg, selector, buffer=None, verbose: bool = False, *,
                 fault_plan: Optional[FaultPlan] = None, episode: int = 0,
                 resume_state: Optional[dict] = None,
                 halt_counter: Optional[dict] = None):
        self.cfg = cfg
        self.selector = selector
        self.buffer = buffer
        self.verbose = verbose
        self.mode = getattr(cfg, "engine_mode", "sync")
        self.executor = resolve_client_executor(cfg)
        self.episode = int(episode)
        self.faults = (fault_plan if fault_plan is not None
                       else FaultPlan.from_config(cfg))
        if self.faults is not None and not len(self.faults):
            self.faults = None
        if self.faults is not None and self.mode == "sync":
            raise ValueError("fault injection needs the event timeline: "
                             "set engine_mode='async'")
        self.ckpt = None
        if getattr(cfg, "checkpoint_dir", ""):
            self.ckpt = EngineCheckpointer(
                cfg.checkpoint_dir, keep=int(getattr(cfg, "checkpoint_keep",
                                                     3)))
        self.ckpt_every = int(getattr(cfg, "checkpoint_every", 0))
        self._halt = halt_counter
        self._resume = resume_state
        self._qpend: List[Any] = []   # (info, device validity array) pairs

    def run(self) -> Dict:
        self.world = build_world(self.cfg)
        rs = self._resume
        if rs is not None:
            if rs.get("mode") != self.mode:
                raise ValueError(
                    f"checkpoint was taken in engine_mode={rs.get('mode')!r}"
                    f" but this engine runs {self.mode!r}")
            # partitions/selector/buffer are mode-independent run state
            self.world.parts = [np.asarray(p) for p in rs["parts"]]  # jaxlint: allow(host-sync-in-hot-path) -- restored checkpoint leaves are host numpy
            self.selector.load_state_dict(rs["selector"])
            if rs.get("buffer") is not None:
                if self.buffer is None:
                    raise ValueError("checkpoint carries replay-buffer state"
                                     " but the engine has no buffer")
                self.buffer.load_state_dict(rs["buffer"])
        if self.mode == "sync":
            return self._run_sync()
        if self.mode == "async":
            return self._run_async()
        raise ValueError(f"unknown engine_mode {self.mode!r} "
                         "(expected 'sync' or 'async')")

    # ------------------------------------------------------------------
    # checkpoint plumbing (shared by both modes)
    # ------------------------------------------------------------------

    def _ckpt_meta(self, step: int) -> dict:
        return {"episode": self.episode, "step": int(step),
                "engine_mode": self.mode,
                "fingerprint": config_fingerprint(self.cfg)}

    # jaxlint: allow(host-sync-in-hot-path) -- checkpoint encode runs at
    # save cadence, off the per-event loop; the save IS the barrier
    def _base_snapshot(self, fleet, global_params, hist) -> dict:
        """Mode-independent slice of the run state (fleet arrays keyed by
        the lint-enforced ``FLEET_CHECKPOINT_FIELDS``, so a new FleetState
        array field fails loudly here rather than silently not resuming)."""
        return {
            "mode": self.mode,
            "fleet": {f: getattr(fleet, f)
                      for f in FLEET_CHECKPOINT_FIELDS},
            "global_params": global_params,
            "hist": hist,
            "parts": [np.asarray(p) for p in self.world.parts],
            "selector": self.selector.state_dict(),
            "buffer": (self.buffer.state_dict()
                       if self.buffer is not None else None),
        }

    def _restore_fleet(self, fleet, arrays: dict):
        fleet = fleet.replace(**arrays)
        if getattr(self.cfg, "fleet_mesh", 0) not in (0, 1):
            from repro.sharding.fleet import maybe_shard_fleet
            fleet = maybe_shard_fleet(fleet, self.cfg.fleet_mesh)
        return fleet

    @staticmethod
    # jaxlint: allow(host-sync-in-hot-path) -- task fields are python
    # scalars; runs only at checkpoint save
    def _encode_task(task: dict, params_table: dict) -> dict:
        """Serializable form of an async task.  A batched task's shared
        ``(BucketResult, row)`` reference becomes its own ``[1, ...]`` row
        slice (the exact tree the completion event would have sliced); a
        perclient task's dispatch-time params snapshot is deduped into
        ``params_table`` by model version (tasks from one dispatch tick
        share one snapshot)."""
        enc = {k: v for k, v in task.items()
               if k not in ("delta_row", "params")}
        if "delta_row" in task:
            dr = task["delta_row"]
            enc["has_delta_row"] = True
            enc["delta1"] = (None if dr is None else jax.tree.map(
                lambda a: a[dr[1]:dr[1] + 1], dr[0].stacked_delta))
        elif "params" in task:
            v = int(task["version"])
            params_table[v] = task["params"]
            enc["params_version"] = v
        return enc

    @staticmethod
    # jaxlint: allow(host-sync-in-hot-path) -- restore-only inverse of
    # _encode_task; manifest values are host state
    def _decode_task(enc: dict, params_table: dict) -> dict:
        task = {k: v for k, v in enc.items()
                if k not in ("delta1", "has_delta_row", "params_version")}
        if enc.get("has_delta_row"):
            d1 = enc["delta1"]
            # row 0 of the restored one-row bucket IS the original slice,
            # so the completion-event jit program (and its output bits)
            # match the uninterrupted run
            task["delta_row"] = (None if d1 is None
                                 else (_RestoredBucket(d1), 0))
        elif "params_version" in enc:
            task["params"] = params_table[int(enc["params_version"])]
        return task

    def _after_save(self):
        if self._halt is None:
            return
        self._halt["remaining"] -= 1
        if self._halt["remaining"] <= 0:
            raise CheckpointHalt(
                "simulated crash: halted after checkpoint save")

    def _flush_quarantine(self, hist) -> None:
        """Drain pending validity verdicts into ``hist["faults"]``.

        Aggregation calls record (context, device-bool-array) pairs; the
        arrays stay on device until a natural barrier (finalize or a
        checkpoint save) flushes them in ONE batched pull.  Entries append
        in aggregation order regardless of when flushes happen, so the
        final ``quarantined`` list is identical across checkpoint cadences
        — which is what makes resumed histories byte-comparable."""
        if not self._qpend:
            return
        f = hist.get("faults")
        if f is None:
            f = hist["faults"] = {"events": [], "quarantined": [],
                                  "n_reaped": 0, "n_quarantined": 0}
        # jaxlint: allow(host-sync-in-hot-path) -- one batched validity pull at a barrier (finalize / checkpoint save), not per aggregation
        vals = jax.device_get([v for _, v in self._qpend])
        for (info, _), v in zip(self._qpend, vals):
            flat = np.atleast_1d(np.asarray(v))
            for j, dev in enumerate(info["devices"]):
                if dev is None or j >= len(flat) or bool(flat[j]):
                    continue
                rec = {k: info[k] for k in info
                       if k not in ("devices", "models")}
                rec["device"] = int(dev)
                rec["m"] = int(info["models"][j])
                f["quarantined"].append(rec)
                f["n_quarantined"] += 1
        self._qpend.clear()

    # ------------------------------------------------------------------
    # sync mode — barrier rounds, bit-for-bit the legacy loop
    # ------------------------------------------------------------------

    def _run_sync(self) -> Dict:
        cfg, w = self.cfg, self.world
        fleet = w.fleet
        global_params = w.global_params
        M = w.n_models
        selector, buffer = self.selector, self.buffer
        marl = selector if isinstance(selector, MarlSelector) else None

        x_dev = y_dev = fleet_steps = None
        if self.executor == "batched":
            # training set stays device-resident: the bucketed executor
            # gathers mini-batches on device instead of per-step host copies
            x_dev, y_dev, fleet_steps = _resident_data(cfg, w)

        # energy scenario (repro.energy): every hook below is gated on the
        # python-level trivial_* flags, so the default config runs the exact
        # pre-scenario program — same traces, same pulls, same bits
        scenario = w.scenario
        gate_avail = not scenario.trivial_availability
        recharge = not scenario.trivial_charge
        budget_active = scenario.budget_active
        tz_host = alive_host = None
        if gate_avail:
            # jaxlint: allow(host-sync-in-hot-path) -- availability-scenario one-time setup pull of the host phase/alive mirrors
            tz_a, alive_a0 = jax.device_get((fleet.tz_phase, fleet.alive))
            tz_host = np.asarray(tz_a, np.float64)
            alive_host = np.asarray(alive_a0, bool).copy()

        w1, w2, w3 = cfg.reward_weights
        rs = self._resume
        if rs is None:
            hist = {"acc": [], "acc_mean": [], "energy": [], "round_time": [],
                    "alive": [], "participants": [], "model_choices": [],
                    "reward": [], "sim_time": [], "straggler_wait_s": [],
                    "dropouts": 0, "idle_time": 0.0, "engine": "sync",
                    "faults": {"events": [], "quarantined": [],
                               "n_reaped": 0, "n_quarantined": 0}}
            prev_acc = float(np.mean(
                fl_server.evaluate(global_params, w.x_val, w.y_val,
                                   family=w.family)))
            e_prev = fleet_total_remaining(fleet)
            sim_time = 0.0
            n_agg = 0
            hotplug_done = False
            t_start = 0
            budget_spent = 0.0
        else:
            fleet = self._restore_fleet(fleet, rs["fleet"])
            global_params = rs["global_params"]
            hist = _upgrade_hist(rs["hist"])
            prev_acc = float(rs["prev_acc"])  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            e_prev = float(rs["e_prev"])  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            sim_time = float(rs["sim_time"])  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            n_agg = int(rs["n_agg"])  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            hotplug_done = bool(rs["hotplug_done"])
            t_start = int(rs["next_round"])  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            budget_spent = float(rs.get("budget_spent", 0.0))  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
        if budget_active and "budget" not in hist:
            hist["budget"] = {"limit": float(cfg.global_budget_j),
                              "spent": 0.0, "overrun": 0.0, "trimmed": 0}
        fleet_dead = False
        budget_exhausted = False

        for t in range(t_start, cfg.n_rounds):
            with telemetry.round_span(t):
                if (cfg.hotplug_n and not hotplug_done
                        and t >= cfg.hotplug_round):
                    # paper Step 1 hot-plug: new devices connect, receive
                    # the global model (implicit — clients always pull W_t),
                    # start with full batteries
                    fleet = fleet_connect(fleet, cfg.n_devices,
                                          cfg.energy_scale)
                    hotplug_done = True
                    if alive_host is not None:
                        alive_host[cfg.n_devices:] = True
                # Top-K budget tracks the CONNECTED fleet (see ISSUE 1 fix).
                n_connected = cfg.n_devices + (cfg.hotplug_n if hotplug_done
                                               else 0)
                k = max(1, int(round(cfg.participation * n_connected)))
                sel_fleet = fleet
                if gate_avail:
                    # diurnal/carbon gate: offline devices look dead to the
                    # selector this round (they auto-abstain, PR 2 semantics)
                    av_host = scenario.available_host(tz_host, sim_time)
                    if alive_host.any() and not (av_host & alive_host).any():
                        # whole surviving fleet is offline — fast-forward the
                        # clock to the next opening instead of burning rounds
                        sim_time = scenario.next_available_host(
                            tz_host[alive_host], sim_time)
                    av_d = scenario.available(fleet, sim_time)
                    sel_fleet = fleet.replace(alive=fleet.alive & av_d)
                sel_kw = {}
                budget_left = overrun = 0.0
                if budget_active:
                    # per-pick hard cap: selectors refuse actions whose cost
                    # alone no longer fits the remaining fleet-wide budget
                    budget_left = float(cfg.global_budget_j) - budget_spent
                    sel_kw["budget_left"] = budget_left
                with telemetry.span("select"):
                    sel = selector.select(sel_fleet, t, k, w.sizes,
                                          w.fractions, cfg.local_epochs,
                                          cfg.batch_size, **sel_kw)
                _check_selection(sel, w.n_total)

                choice = np.asarray(sel.model_choice, np.int64)
                active = choice >= 0
                m_idx = np.clip(choice, 0, M - 1)
                with telemetry.span("charge"):
                    t_tra_m, t_com_m, e_tra_m, e_com_m = (
                        fleet_cost_matrix_jit(fleet, w.sizes, w.fractions,
                                              cfg.local_epochs,
                                              cfg.batch_size))
                    # gather each device's chosen-model column on device,
                    # charge, then pull everything the round head needs in
                    # ONE sync
                    m_col = jnp.asarray(m_idx)[:, None]
                    t_cost_d = jnp.take_along_axis(t_tra_m + t_com_m, m_col,
                                                   1)[:, 0]
                    need_d = jnp.take_along_axis(e_tra_m + e_com_m, m_col,
                                                 1)[:, 0]
                    had_picks = bool(active.any())
                    budget_starved = False
                    if budget_active and not had_picks:
                        # no picks at all: decide whether the per-pick budget
                        # gate (not drained batteries) closed the round — if
                        # some alive device could fund its cheapest submodel
                        # from its OWN battery but not from the remaining
                        # global budget, further rounds can never dispatch
                        # either
                        # jaxlint: allow(host-sync-in-hot-path) -- budget-scenario termination disambiguation; runs only when a round selects nobody
                        mn_a, rem_a, al_a = jax.device_get(
                            ((e_tra_m + e_com_m).min(axis=1), fleet.remaining,
                             fleet.alive))
                        mn = np.asarray(mn_a, np.float64)
                        own_ok = (np.asarray(al_a, bool)
                                  & (mn < np.asarray(rem_a, np.float64)))
                        if own_ok.any() and mn[own_ok].min() > budget_left:
                            budget_starved = True
                    if budget_active:
                        # cumulative hard cap: each pick respected the
                        # per-pick budget gate, but together they can still
                        # overrun — trim in selection order and charge the
                        # trimmed cost to the round's reward as an overrun
                        # penalty
                        # jaxlint: allow(host-sync-in-hot-path) -- budget-scenario-only extra pull: per-pick costs for the cumulative cap
                        need_h = np.asarray(jax.device_get(need_d),
                                            np.float64)
                        left = budget_left
                        for i in sel.participants:
                            if not active[i]:
                                continue
                            if need_h[i] <= left + 1e-9:
                                left -= float(need_h[i])
                            else:
                                active[i] = False
                                overrun += float(need_h[i])
                        # attempted cost counts as spent (deaths waste no
                        # more than their attempt), so the cap can never be
                        # overdrawn
                        budget_spent += float(need_h[active].sum())
                    fleet, ok_d = fleet_charge_jit(fleet, need_d,
                                                   jnp.asarray(active))
                    # jaxlint: allow(host-sync-in-hot-path) -- the one batched pull per round head: charge outcome + per-device round times
                    t_cost, ok = jax.device_get((t_cost_d, ok_d))
                hist["dropouts"] += int((active & ~ok).sum())
                survivors = active & ok
                t_round = (float(t_cost[survivors].max())
                           if survivors.any() else 0.0)
                # straggler wait: finished participants idle at the barrier
                idle_round = float((t_round - t_cost[survivors]).sum())
                if recharge and t_round > 0.0:
                    # harvesting: alive devices trickle-charge while the
                    # round runs (midpoint-rate rectangle over
                    # [sim_time, +t_round])
                    fleet = scenario.apply_charge(fleet, sim_time,
                                                  sim_time + t_round)

                # contributors: survivors with local data (large-fleet
                # Dirichlet splits can leave a device with no samples — it
                # still paid the round's mostly-comm energy but has nothing
                # to contribute)
                cohort = [i for i in sel.participants
                          if survivors[i] and len(w.parts[i])]
                if self.executor == "batched" and cohort:
                    # whole cohort in <= n_buckets jit dispatches (one per
                    # populated submodel index), stacked deltas straight
                    # into the Pallas layer-agg aggregation for DR-FL
                    res = _run_batched_cohort(
                        cfg, w, global_params, cohort,
                        [int(choice[i]) for i in cohort],
                        [fl_client.client_update_seed(cfg.seed, t, i)
                         for i in cohort], x_dev, y_dev, fleet_steps)
                    if cfg.method == "drfl":
                        global_params, valid = (
                            fl_server.aggregate_drfl_stacked(
                                global_params,
                                [(b.model_idx, b.stacked_delta, b.weights,
                                  None) for b in res.buckets],
                                server_lr=cfg.server_lr, family=w.family,
                                with_stats=True))
                        # one validity entry per client row, bucket order
                        devs = [d for b in res.buckets
                                for d in b.participants]
                        models = [b.model_idx for b in res.buckets
                                  for _ in b.participants]
                        if valid is not None:
                            self._qpend.append((
                                {"devices": devs, "models": models,
                                 "round": t, "time": sim_time}, valid))
                    else:
                        contribs = res.unstacked()
                        global_params, valid = fl_server.aggregate_sliced(
                            global_params, [c[2] for c in contribs],
                            [c[3] for c in contribs], with_stats=True)
                        self._qpend.append((
                            {"devices": [c[0] for c in contribs],
                             "models": [c[1] for c in contribs], "round": t,
                             "time": sim_time}, valid))
                        del contribs
                    # the round's client deltas die with its aggregation,
                    # not when the next round's cohort replaces them: the
                    # next bucket program's output would else sit beside
                    # a second round of stacked deltas on the device (so
                    # no loop variable above may hold a bucket either)
                    del res
                    n_agg += 1
                elif cohort:
                    deltas, idxs, weights = [], [], []
                    for i in cohort:
                        m = int(choice[i])
                        xi = w.x_tr[w.parts[i]]
                        yi = w.y_tr[w.parts[i]]
                        upd_seed = fl_client.client_update_seed(cfg.seed, t,
                                                                i)
                        d_, _ = _client_update(cfg, w.family, global_params,
                                               m, xi, yi, upd_seed)
                        deltas.append(d_)
                        idxs.append(m)
                        weights.append(float(len(xi)))
                    if cfg.method == "drfl":
                        global_params, valid = fl_server.aggregate_drfl(
                            global_params, deltas, idxs, weights,
                            server_lr=cfg.server_lr, family=w.family,
                            with_stats=True)
                    else:
                        global_params, valid = fl_server.aggregate_sliced(
                            global_params, deltas, weights, with_stats=True)
                    self._qpend.append((
                        {"devices": list(cohort), "models": idxs,
                         "round": t, "time": sim_time}, valid))
                    n_agg += 1

                accs = fl_server.evaluate(global_params, w.x_val, w.y_val,
                                          family=w.family)
                acc = float(np.mean(accs))
                # jaxlint: allow(host-sync-in-hot-path) -- one batched pull per round tail: reward energy term + alive telemetry
                e_now_a, alive_a = jax.device_get((fleet.remaining.sum(),
                                                   fleet.alive))
                e_now = float(e_now_a)
                reward = (w1 * (acc - prev_acc) - w2 * (e_prev - e_now)
                          - w3 * (t_round / 60.0))
                if budget_active and overrun:
                    # budget-overrun penalty: energy the fleet PROPOSED to
                    # spend past the global cap, priced like wasted joules
                    reward -= w2 * overrun
                sim_time += t_round
                selector.observe_reward(reward, sim_time=sim_time)
                prev_acc, e_prev = acc, e_now

                if marl:
                    if ((t + 1) % cfg.marl_train_every == 0
                            and marl.ep_rewards):
                        _marl_train(marl, buffer, hist, fleet, t + 1,
                                    cfg.marl_updates_per_round)

                alive_now = int(alive_a.sum())
                hist["acc"].append(np.asarray(accs))
                hist["acc_mean"].append(acc)
                hist["energy"].append(e_now)
                hist["round_time"].append(t_round)
                hist["alive"].append(alive_now)
                hist["participants"].append(list(sel.participants))
                hist["model_choices"].append(
                    [sel.model_choice[i] for i in sel.participants])
                hist["reward"].append(reward)
                hist["sim_time"].append(sim_time)
                hist["straggler_wait_s"].append(idle_round)
                hist["idle_time"] += idle_round
                if alive_host is not None:
                    alive_host = np.asarray(alive_a, bool).copy()
                if budget_active:
                    hist["budget"]["spent"] = budget_spent
                    hist["budget"]["overrun"] += overrun
                    if overrun:
                        hist["budget"]["trimmed"] += 1
                if self.verbose:
                    print(f"  round {t:3d}: acc={acc:.3f} exits="
                          f"{np.round(np.asarray(accs), 3)} alive={alive_now}"
                          f" energy={e_now:,.0f}J time={t_round:.1f}s"
                          f" r={reward:+.2f}")
                if alive_now == 0:
                    fleet_dead = True
                    break
                if budget_active and (
                        float(cfg.global_budget_j) - budget_spent <= 1e-9
                        or budget_starved
                        or (had_picks and not active.any())):
                    # nothing left to fund (or the whole round's picks were
                    # trimmed): stop here rather than ticking unfunded rounds
                    budget_exhausted = True
                    break
                if (self.ckpt is not None and self.ckpt_every > 0
                        and (t + 1) % self.ckpt_every == 0):
                    self._flush_quarantine(hist)
                    state = self._base_snapshot(fleet, global_params, hist)
                    state.update(next_round=t + 1, prev_acc=prev_acc,
                                 e_prev=e_prev, sim_time=sim_time, n_agg=n_agg,
                                 hotplug_done=hotplug_done,
                                 budget_spent=budget_spent)
                    self.ckpt.save(state, self._ckpt_meta(t + 1))
                    self._after_save()

        hist["terminated"] = {
            "reason": ("budget_exhausted" if budget_exhausted
                       else "fleet_dead" if fleet_dead else "completed"),
            "rounds": len(hist["acc_mean"]), "n_rounds": cfg.n_rounds,
            "sim_time": sim_time,
        }
        if budget_exhausted:
            hist["terminated"]["budget"] = "energy"
        hist["n_aggregations"] = n_agg
        hist["sim_time_total"] = sim_time
        return self._finalize(hist, global_params)

    # ------------------------------------------------------------------
    # async mode — event heap over per-device virtual clocks
    # ------------------------------------------------------------------

    def _run_async(self) -> Dict:
        cfg, w = self.cfg, self.world
        fleet = w.fleet
        global_params = w.global_params
        selector, buffer = self.selector, self.buffer
        marl = selector if isinstance(selector, MarlSelector) else None
        decay = getattr(cfg, "staleness_decay", 0.5)
        eval_every = max(1, int(getattr(cfg, "async_eval_every", 1)))
        horizon = float(getattr(cfg, "async_time_horizon", 0.0))
        budget = int(getattr(cfg, "async_task_budget", 0)
                     or sync_task_budget(cfg))
        w1, w2, w3 = cfg.reward_weights

        # energy scenario hooks — python-gated like sync, so the default
        # config dispatches the exact pre-scenario event timeline
        scenario = w.scenario
        gate_avail = not scenario.trivial_availability
        recharge = not scenario.trivial_charge
        budget_active = scenario.budget_active
        tz_host = None
        if gate_avail:
            # jaxlint: allow(host-sync-in-hot-path) -- availability-scenario one-time setup pull of the host phase mirror
            tz_host = np.asarray(jax.device_get(fleet.tz_phase), np.float64)

        x_dev = y_dev = fleet_steps = None
        if self.executor == "batched":
            x_dev, y_dev, fleet_steps = _resident_data(cfg, w)

        deadline_factor = float(getattr(cfg, "task_deadline_factor", 4.0))
        # per-task deadlines (and their reap events) exist only when faults
        # are injected: a reap pop re-runs refill(), which can consume
        # selector RNG, so clean runs must not see ANY reap events if their
        # timelines are to stay bit-for-bit with earlier releases
        reaping = self.faults is not None
        tasks: Dict[int, dict] = {}        # tid -> task (shared with heap)
        task_by_dev: Dict[int, dict] = {}  # device -> its in-flight task
        disconnected: set = set()
        corrupt_pending: Dict[int, list] = {}  # dev -> [(payload, ev_idx)]
        rs = self._resume
        if rs is None:
            hist = {"acc": [], "acc_mean": [], "energy": [], "round_time": [],
                    "alive": [], "participants": [], "model_choices": [],
                    "reward": [], "sim_time": [], "straggler_wait_s": [],
                    "staleness": [], "task_log": [], "lost": [],
                    "dropouts": 0, "idle_time": 0.0, "wait_for_work": 0.0,
                    "hotplug": None, "engine": "async",
                    "faults": {"events": [], "quarantined": [],
                               "n_reaped": 0, "n_quarantined": 0}}
            acc_prev = float(np.mean(
                fl_server.evaluate(global_params, w.x_val, w.y_val,
                                   family=w.family)))

            state = dict(now=0.0, version=0, seq=0, vround=0,
                         tasks_started=0, completions=0, inflight=0,
                         n_cohorts=0, next_commit=0, last_event=0.0,
                         hotplug_done=not cfg.hotplug_n, acc_prev=acc_prev,
                         window_t0=0.0, window_reward=0.0, window_idle=0.0,
                         window_lost=0, tid=0,
                         budget_spent=0.0, budget_blocked=False,
                         last_charge_t=0.0)
            heap: list = []
            cohorts: Dict[int, dict] = {}   # one per selector.select call
            last_done: Dict[int, float] = {}
            window_devices: List[int] = []
            window_models: List[int] = []
            # authoritative virtual clocks, host-side float64: the jax-backend
            # FleetState stores busy_until in float32 (x64 is disabled), whose
            # ~8ms resolution at ~6.5e4 sim-seconds could mark a mid-task
            # device idle; fleet.busy_until is kept as an observability mirror
            # jaxlint: allow(host-sync-in-hot-path) -- one-time setup pull of the host clock mirror
            busy64 = np.asarray(fleet.busy_until, np.float64).copy()
            # alive mirror, maintained from values the loop pulls anyway
            # (charge outcomes, hotplug) so the per-event idle check costs
            # no device sync
            # jaxlint: allow(host-sync-in-hot-path) -- one-time setup pull of the host alive mirror
            alive_host = np.asarray(fleet.alive, bool).copy()
            if self.faults is not None:
                # injected churn rides the same heap as completions; seq
                # pre-assignment makes fault-vs-completion ties deterministic
                for ev in self.faults.events:
                    heapq.heappush(
                        heap, (float(ev.time), state["seq"], "fault",  # jaxlint: allow(host-sync-in-hot-path) -- FaultEvent fields are python scalars; startup plan expansion
                               {"kind": ev.kind, "device": int(ev.device),  # jaxlint: allow(host-sync-in-hot-path) -- FaultEvent fields are python scalars
                                "duration": float(ev.duration),  # jaxlint: allow(host-sync-in-hot-path) -- FaultEvent fields are python scalars
                                "payload": ev.payload}))
                    state["seq"] += 1
        else:
            fleet = self._restore_fleet(fleet, rs["fleet"])
            global_params = rs["global_params"]
            hist = _upgrade_hist(rs["hist"])
            state = dict(rs["state"])
            state.pop("window_wall0", None)
            state.setdefault("budget_spent", 0.0)
            state.setdefault("budget_blocked", False)
            state.setdefault("last_charge_t", float(state["now"]))
            cohorts = {int(k): dict(v) for k, v in rs["cohorts"].items()}  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            last_done = {int(k): float(v)  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
                         for k, v in rs["last_done"].items()}
            window_devices = [int(i) for i in rs["window_devices"]]  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            window_models = [int(m) for m in rs["window_models"]]  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            busy64 = rs["busy64"]
            alive_host = rs["alive_host"]
            disconnected = set(int(i) for i in rs["disconnected"])  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
            corrupt_pending = {int(k): [tuple(x) for x in v]  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
                               for k, v in rs["corrupt_pending"].items()}
            for tid, enc in rs["tasks"].items():
                tasks[int(tid)] = self._decode_task(enc, rs["params_table"])
            # the serialized heap list was already heap-ordered, so
            # restoring it verbatim preserves the invariant; done/reap
            # entries re-share one task object per tid
            heap = [(float(tt), int(sq), kind,  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
                     tasks[int(ref)] if kind in ("done", "reap")  # jaxlint: allow(host-sync-in-hot-path) -- one-time resume, host values
                     else dict(ref) if kind == "fault" else None)
                    for tt, sq, kind, ref in rs["heap"]]
            for task in tasks.values():
                if not task.get("done") and not task.get("reaped"):
                    task_by_dev[task["device"]] = task
        if budget_active and "budget" not in hist:
            hist["budget"] = {"limit": float(cfg.global_budget_j),
                              "spent": 0.0, "overrun": 0.0, "trimmed": 0}

        def n_connected():
            return cfg.n_devices + (cfg.hotplug_n if state["hotplug_done"]
                                    else 0)

        def top_k():
            return max(1, int(round(cfg.participation * n_connected())))

        def credit(cid, amount):
            cohorts[cid]["reward"] += amount
            state["window_reward"] += amount

        def commit_ready():
            # flush cohort rewards to the selector IN DISPATCH ORDER so the
            # MARL episode trace stays (obs_t, action_t, reward_t)-aligned
            # even when later dispatches complete first
            while (state["next_commit"] < state["n_cohorts"]
                   and cohorts[state["next_commit"]]["pending"] == 0):
                c = cohorts.pop(state["next_commit"])
                selector.observe_reward(c["reward"], sim_time=state["now"])
                state["next_commit"] += 1

        def maybe_hotplug(force: bool = False):
            nonlocal fleet
            if state["hotplug_done"] or (not force
                                         and state["vround"]
                                         < cfg.hotplug_round):
                return
            now = state["now"]
            k_before = top_k()
            fleet = fleet_connect(fleet, cfg.n_devices, cfg.energy_scale,
                                  now=now)
            busy64[cfg.n_devices:] = now
            alive_host[cfg.n_devices:] = True    # fleet_connect: joins live
            state["hotplug_done"] = True
            hist["hotplug"] = {
                "sim_time": now, "vround": state["vround"],
                "version": state["version"], "k_before": k_before,
                "k_after": top_k(),
                # jaxlint: allow(host-sync-in-hot-path) -- hotplug happens once per run; telemetry pull
                "join_remaining": [float(r) for r in np.asarray(
                    fleet.remaining)[cfg.n_devices:]],
            }

        def try_dispatch(n_sel) -> int:
            nonlocal fleet, alive_host
            now = state["now"]
            if recharge and now > state["last_charge_t"]:
                # harvest the idle gap since the last dispatch tick BEFORE
                # costing/charging, so e_before reflects the topped-up fleet
                fleet = scenario.apply_charge(fleet, state["last_charge_t"],
                                              now)
                state["last_charge_t"] = now
            idle = alive_host & (busy64 <= now + 1e-9)
            if gate_avail:
                # offline devices (diurnal window / carbon curfew) simply
                # aren't idle candidates; the heap-empty wake event below
                # reopens the timeline when everyone is offline
                idle &= scenario.available_host(tz_host, now)
            if not idle.any():
                return 0
            budget_left = 0.0
            if budget_active:
                budget_left = (float(cfg.global_budget_j)
                               - state["budget_spent"])
                if budget_left <= 1e-9:
                    state["budget_blocked"] = True
                    return 0
            cid = state["n_cohorts"]
            state["n_cohorts"] += 1
            cohorts[cid] = {"pending": 0, "reward": 0.0}
            alive_mask = (jnp.asarray(idle) if fleet_is_jax(fleet) else idle)
            sel_kw = {"budget_left": budget_left} if budget_active else {}
            with telemetry.span("select"):
                sel = selector.select(fleet.replace(alive=alive_mask),
                                      state["vround"], n_sel, w.sizes,
                                      w.fractions, cfg.local_epochs,
                                      cfg.batch_size, **sel_kw)
            _check_selection(sel, w.n_total)
            choice = np.asarray(sel.model_choice, np.int64)
            active = choice >= 0
            if active.any():
                m_idx = np.clip(choice, 0, w.n_models - 1)
                # the charge is two spans: costs here, the charge below
                with telemetry.span("charge"):
                    t_tra, t_com, e_tra, e_com = fleet_cost_matrix_jit(
                        fleet, w.sizes, w.fractions, cfg.local_epochs,
                        cfg.batch_size)
                    m_col = jnp.asarray(m_idx)[:, None]
                    need_d = jnp.take_along_axis(e_tra + e_com, m_col,
                                                 1)[:, 0]
                    t_cost_d = jnp.take_along_axis(t_tra + t_com, m_col,
                                                   1)[:, 0]
                    need_h = None
                    if budget_active:
                        # jaxlint: allow(host-sync-in-hot-path) -- budget-scenario variant of the same first batched pull (extra values, same sync count)
                        t_cost, need_h = jax.device_get((t_cost_d, need_d))
                        need_h = np.asarray(need_h, np.float64)
                    else:
                        # jaxlint: allow(host-sync-in-hot-path) -- first of the two batched pulls per dispatch tick: per-task times for the event heap
                        t_cost = jax.device_get(t_cost_d)
                if horizon > 0:
                    # only send work that can land inside the time budget
                    active &= (now + t_cost) <= horizon + 1e-9
                allow = budget - state["tasks_started"]
                kept = [i for i in sel.participants if active[i]][:allow]
                if budget_active:
                    # cumulative cap, trimmed in selection order (sync rule)
                    left, funded, overrun = budget_left, [], 0.0
                    for i in kept:
                        if need_h[i] <= left + 1e-9:
                            left -= float(need_h[i])
                            funded.append(i)
                        else:
                            overrun += float(need_h[i])
                    if overrun:
                        credit(cid, -w2 * overrun)  # overrun penalty
                        hist["budget"]["overrun"] += overrun
                        hist["budget"]["trimmed"] += 1
                    if kept and not funded:
                        state["budget_blocked"] = True
                    kept = funded
                active = np.zeros(w.n_total, bool)
                active[kept] = True
            if not active.any():
                if budget_active and not state["budget_blocked"]:
                    # nothing dispatched: was it the budget's per-pick gate,
                    # or genuinely drained batteries?  Blocked only if some
                    # idle device could afford its cheapest submodel from
                    # its OWN battery but not from the remaining budget.
                    _, _, e_tra, e_com = fleet_cost_matrix_jit(
                        fleet, w.sizes, w.fractions, cfg.local_epochs,
                        cfg.batch_size)
                    # jaxlint: allow(host-sync-in-hot-path) -- budget-scenario termination disambiguation; runs only when a dispatch comes back empty
                    min_need_a, rem_a = jax.device_get(
                        ((e_tra + e_com).min(axis=1), fleet.remaining))
                    min_need = np.asarray(min_need_a, np.float64)
                    own_ok = idle & (min_need < np.asarray(rem_a,
                                                           np.float64))
                    if own_ok.any() and min_need[own_ok].min() > budget_left:
                        state["budget_blocked"] = True
                return 0
            with telemetry.span("charge"):
                e_before_d = fleet.remaining.sum()
                fleet, ok_d = fleet_charge_jit(fleet, need_d,
                                               jnp.asarray(active))
                # jaxlint: allow(host-sync-in-hot-path) -- second batched pull per dispatch tick: charge outcome + energy reward terms
                ok, e_before_a, e_after_a = jax.device_get(
                    (ok_d, e_before_d, fleet.remaining.sum()))
            e_before, e_after = float(e_before_a), float(e_after_a)
            # fleet_charge kills attempted-but-unaffordable devices; fold
            # the same deaths into the host mirror
            alive_host &= ~(active & ~ok)
            hist["dropouts"] += int((active & ~ok).sum())
            # energy term at SEND time (includes batteries wasted by deaths)
            credit(cid, -w2 * (e_before - e_after))
            if budget_active:
                # attempted cost counts as spent (a death wastes at most its
                # attempt), so the global cap can never be overdrawn
                state["budget_spent"] += float(need_h[active].sum())
                state["budget_blocked"] = False
                hist["budget"]["spent"] = state["budget_spent"]
            started = [i for i in sel.participants if active[i] and ok[i]]
            if not started:
                return 0
            busy64[np.asarray(started)] = now + t_cost[np.asarray(started)]
            fleet = fleet_set_busy(fleet, started,
                                   now + t_cost[np.asarray(started)])
            # micro-bucket: tasks sharing this dispatch tick train against
            # the SAME pulled snapshot, so the bucketed executor runs them
            # as <= n_buckets jit programs NOW and the completion events
            # just consume the precomputed deltas (semantically identical —
            # a client's delta depends only on dispatch-time state).  Each
            # task stores its (shared) BucketResult + row, not a sliced
            # per-client tree — one slice happens at aggregation time.
            rows_by_dev: Dict[int, Any] = {}
            if self.executor == "batched":
                with_data = [i for i in started if len(w.parts[i])]
                if with_data:
                    res = _run_batched_cohort(
                        cfg, w, global_params, with_data,
                        [int(choice[i]) for i in with_data],
                        [fl_client.client_update_seed(cfg.seed, cid, i)
                         for i in with_data], x_dev, y_dev, fleet_steps)
                    for b in res.buckets:
                        for r, dev in enumerate(b.participants):
                            rows_by_dev[dev] = (b, r)
            for i in started:
                if i in last_done:            # wait-for-work since last task
                    hist["wait_for_work"] += now - last_done[i]
                task = {
                    "tid": state["tid"], "device": i, "m": int(choice[i]),
                    "version": state["version"],
                    "cohort": cid, "dispatch": cid, "t0": now,
                    "t_cost": float(t_cost[i]),
                }
                state["tid"] += 1
                if self.executor == "batched":
                    task["delta_row"] = rows_by_dev.get(i)
                else:
                    # per-client path trains lazily at the completion event
                    task["params"] = global_params
                tasks[task["tid"]] = task
                task_by_dev[i] = task
                heapq.heappush(heap, (now + float(t_cost[i]), state["seq"],
                                      "done", task))
                state["seq"] += 1
                if reaping:
                    # deadline strictly beyond the completion event: a lost
                    # task's slot is reclaimed here, a healthy task's reap
                    # pops as a no-op after its own completion
                    task["deadline"] = now + deadline_factor * float(
                        t_cost[i])
                    heapq.heappush(heap, (task["deadline"], state["seq"],
                                          "reap", task))
                    state["seq"] += 1
            cohorts[cid]["pending"] = len(started)
            state["tasks_started"] += len(started)
            state["inflight"] += len(started)
            return len(started)

        def refill():
            while (state["tasks_started"] < budget
                   and state["inflight"] < top_k()):
                if horizon > 0 and state["now"] >= horizon:
                    break
                n_sel = min(top_k() - state["inflight"],
                            budget - state["tasks_started"])
                if try_dispatch(n_sel) == 0:
                    break

        def emit_row():
            now = state["now"]
            accs = fl_server.evaluate(global_params, w.x_val, w.y_val,
                                      family=w.family)
            acc = float(np.mean(accs))
            # re-baseline the accuracy term here so eval_every > 1 doesn't
            # leak un-credited progress into later event rewards
            state["window_reward"] += w1 * (acc - state["acc_prev"])
            state["acc_prev"] = acc
            # jaxlint: allow(host-sync-in-hot-path) -- one batched telemetry pull per virtual round
            e_now_a, alive_a = jax.device_get((fleet.remaining.sum(),
                                               fleet.alive))
            e_now, alive_now = float(e_now_a), int(alive_a.sum())
            hist["acc"].append(np.asarray(accs))
            hist["acc_mean"].append(acc)
            hist["energy"].append(e_now)
            hist["round_time"].append(now - state["window_t0"])
            hist["alive"].append(alive_now)
            hist["participants"].append(list(window_devices))
            hist["model_choices"].append(list(window_models))
            hist["reward"].append(state["window_reward"])
            hist["sim_time"].append(now)
            hist["straggler_wait_s"].append(state["window_idle"])
            hist["lost"].append(state["window_lost"])
            if self.verbose:
                print(f"  vround {state['vround']:3d}: acc={acc:.3f}"
                      f" alive={alive_now} energy={e_now:,.0f}J"
                      f" t={now:.1f}s r={state['window_reward']:+.2f}")
            window_devices.clear()
            window_models.clear()
            state["window_t0"] = now
            state["window_reward"] = 0.0
            state["window_idle"] = 0.0
            state["window_lost"] = 0
            state["vround"] += 1

        def maybe_emit():
            # lost (reaped) tasks count toward the virtual-round quota so
            # heavy churn still advances rounds — a window where every task
            # died emits a zero-participant row instead of stalling
            if len(window_devices) + state["window_lost"] >= top_k():
                emit_row()
                maybe_hotplug()

        def process_completion(task):
            nonlocal global_params
            now = state["now"]
            i = task["device"]
            task["done"] = True
            if task_by_dev.get(i) is task:
                del task_by_dev[i]
            state["inflight"] -= 1
            last_done[i] = now
            staleness = state["version"] - task["version"]
            cid = task["cohort"]
            cohorts[cid]["pending"] -= 1
            # time term pays the VIRTUAL TIME ADVANCED by this event (the
            # gap since the previous one), not the task's own duration:
            # gaps telescope to the window duration, so a virtual round's
            # total time penalty matches sync's t_round / FLEnv's event
            # gaps rather than k-fold overcharging overlapped tasks
            credit(cid, -w3 * ((now - state["last_event"]) / 60.0))
            state["last_event"] = now
            # straggler wait: the update is aggregated at this very event,
            # so it waits (now - finish_time) = 0 — computed, not assumed
            agg_wait = now - (task["t0"] + task["t_cost"])
            hist["idle_time"] += agg_wait
            state["window_idle"] += agg_wait
            n_i = len(w.parts[i])
            aggregated = False
            if n_i:
                poison_val = None
                if corrupt_pending.get(i):
                    # an armed "corrupt" fault fires on this device's next
                    # completed delta; the aggregation-side quarantine must
                    # keep it out of the global params (asserted by tests)
                    payload, ev_idx = corrupt_pending[i].pop(0)
                    poison_val = poison_payload(payload)
                    ev_rec = hist["faults"]["events"][ev_idx]
                    ev_rec["outcome"] = "poisoned"
                    ev_rec["poisoned_version"] = state["version"]
                batched = "delta_row" in task
                if batched:
                    # bucketed executor: delta precomputed at the dispatch
                    # tick against the snapshot pulled there; slice this
                    # client's row out of the shared bucket result now
                    bucket, row = task["delta_row"]
                else:
                    # clients train on the model snapshot they PULLED at
                    # dispatch; the server reconciles drift via staleness
                    seed = fl_client.client_update_seed(cfg.seed,
                                                        task["dispatch"], i)
                    delta, _ = _client_update(cfg, w.family, task["params"],
                                              task["m"],
                                              w.x_tr[w.parts[i]],
                                              w.y_tr[w.parts[i]], seed)
                qinfo = {"devices": [i], "models": [task["m"]],
                         "version": state["version"], "time": now}
                if cfg.method == "drfl":
                    if batched:
                        delta_1 = jax.tree.map(
                            lambda a: a[row:row + 1], bucket.stacked_delta)
                        if poison_val is not None:
                            delta_1 = jax.tree.map(
                                lambda a: jnp.full_like(a, poison_val),
                                delta_1)
                        global_params, valid = (
                            fl_server.aggregate_drfl_stacked(
                                global_params,
                                [(task["m"], delta_1, [float(n_i)],
                                  [staleness])],
                                server_lr=cfg.server_lr,
                                staleness_decay=decay,
                                family=w.family, with_stats=True))
                    else:
                        if poison_val is not None:
                            delta = jax.tree.map(
                                lambda a: jnp.full_like(a, poison_val),
                                delta)
                        global_params, valid = fl_server.aggregate_drfl(
                            global_params, [delta], [task["m"]],
                            [float(n_i)], server_lr=cfg.server_lr,
                            staleness=[staleness], staleness_decay=decay,
                            family=w.family, with_stats=True)
                else:
                    if batched:
                        delta = jax.tree.map(lambda a: a[row],
                                             bucket.stacked_delta)
                    if poison_val is not None:
                        delta = jax.tree.map(
                            lambda a: jnp.full_like(a, poison_val), delta)
                    a = fl_server.staleness_scale(staleness, decay)
                    if a != 1.0:
                        delta = jax.tree.map(
                            lambda u: (u * a).astype(u.dtype), delta)
                    global_params, valid = fl_server.aggregate_sliced(
                        global_params, [delta], [float(n_i)],
                        with_stats=True)
                if valid is not None:
                    self._qpend.append((qinfo, valid))
                state["version"] += 1
                aggregated = True
            hist["staleness"].append(staleness)
            hist["task_log"].append({
                "device": i, "dispatch": task["dispatch"],
                "version": task["version"], "staleness": staleness,
                "m": task["m"], "t_dispatch": task["t0"], "t_done": now,
            })
            # per-aggregation accuracy evals exist to feed event-time
            # rewards; for non-learning selectors observe_reward is a
            # no-op, so only the virtual-round boundary evaluates
            if marl and aggregated and state["version"] % eval_every == 0:
                accs = fl_server.evaluate(global_params, w.x_val, w.y_val,
                                          family=w.family)
                acc = float(np.mean(accs))
                credit(cid, w1 * (acc - state["acc_prev"]))
                state["acc_prev"] = acc
            window_devices.append(i)
            window_models.append(task["m"])
            state["completions"] += 1
            maybe_emit()

        def process_reap(task):
            # a lost task's deadline passed: reclaim its in-flight slot and
            # settle its cohort so commit_ready can flush in dispatch order.
            # Healthy tasks completed before their deadline — their reap
            # pops as a pure no-op.
            nonlocal fleet
            if (task.get("done") or task.get("reaped")
                    or not task.get("lost")):
                return
            task["reaped"] = True
            now = state["now"]
            i = task["device"]
            if task_by_dev.get(i) is task:
                del task_by_dev[i]
            state["inflight"] -= 1
            cohorts[task["cohort"]]["pending"] -= 1
            # the lost task's cohort pays for the virtual time its silence
            # stalled the timeline (same telescoping rule as completions)
            credit(task["cohort"], -w3 * ((now - state["last_event"])
                                          / 60.0))
            state["last_event"] = now
            busy64[i] = min(busy64[i], now)
            fleet = fleet_set_busy(fleet, [i], float(busy64[i]))  # jaxlint: allow(host-sync-in-hot-path) -- busy64 is the float64 host mirror, no device sync
            hist["faults"]["n_reaped"] += 1
            state["window_lost"] += 1
            hist["task_log"].append({
                "device": i, "dispatch": task["dispatch"],
                "version": task["version"], "staleness": None,
                "m": task["m"], "t_dispatch": task["t0"], "t_done": None,
                "lost": True, "reaped_at": now,
            })
            maybe_emit()

        def process_fault(ev):
            nonlocal fleet
            now = state["now"]
            i = int(ev["device"])
            kind = ev["kind"]
            entry = {"time": now, "kind": kind, "device": i,
                     "injected": kind != "rejoin"}
            task = task_by_dev.get(i)
            if kind == "rejoin":
                if i in disconnected:
                    disconnected.discard(i)
                    fleet = fleet_set_alive(fleet, [i], True)
                    alive_host[i] = True
                    busy64[i] = now
                    fleet = fleet_set_busy(fleet, [i], now)
                    entry["outcome"] = "rejoined"
                else:
                    # the device crash-died while disconnected — stays dead
                    entry["outcome"] = "noop"
            elif kind == "crash":
                if not alive_host[i]:
                    entry["outcome"] = "already_dead"
                else:
                    # jaxlint: allow(host-sync-in-hot-path) -- one scalar pull per injected crash event (plan-bounded, not per tick)
                    e_lost = float(jax.device_get(fleet.remaining[i]))
                    fleet = fleet_kill(fleet, [i])
                    alive_host[i] = False
                    entry["e_lost"] = e_lost
                    if task is not None and not task.get("lost"):
                        # mid-task: the cohort that picked this device eats
                        # the wasted battery, so MARL learns flakiness
                        task["lost"] = True
                        credit(task["cohort"], -w2 * e_lost)
                        entry["outcome"] = "crash_mid_task"
                    else:
                        entry["outcome"] = "crash_idle"
            elif kind == "timeout":
                if task is None or task.get("lost"):
                    entry["outcome"] = "no_inflight_task"
                else:
                    # straggler: silent until the deadline reaps the task;
                    # the device itself survives with its battery
                    task["lost"] = True
                    busy64[i] = task["deadline"]
                    fleet = fleet_set_busy(fleet, [i], task["deadline"])
                    entry["outcome"] = "timed_out"
                    entry["reap_at"] = task["deadline"]
            elif kind == "disconnect":
                if not alive_host[i]:
                    entry["outcome"] = "already_dead"
                else:
                    alive_host[i] = False
                    fleet = fleet_set_alive(fleet, [i], False)
                    disconnected.add(i)
                    if task is not None and not task.get("lost"):
                        task["lost"] = True
                        entry["outcome"] = "disconnect_mid_task"
                    else:
                        entry["outcome"] = "disconnected"
                    t_back = now + max(float(ev.get("duration", 0.0)), 1e-6)
                    heapq.heappush(heap, (t_back, state["seq"], "fault",
                                          {"kind": "rejoin", "device": i}))
                    state["seq"] += 1
                    entry["rejoin_at"] = t_back
            elif kind == "corrupt":
                entry["payload"] = ev.get("payload") or "nan"
                entry["outcome"] = "armed"
            hist["faults"]["events"].append(entry)
            if kind == "corrupt":
                corrupt_pending.setdefault(i, []).append(
                    (entry["payload"], len(hist["faults"]["events"]) - 1))

        def save_checkpoint():
            # quarantine verdicts flush first so the serialized hist is
            # self-consistent; heap entries serialize task payloads by tid
            # (done+reap share one object) and perclient param snapshots
            # dedup by version
            self._flush_quarantine(hist)
            params_table: Dict[int, Any] = {}
            tasks_enc: Dict[int, Any] = {}
            heap_enc = []
            for tt, sq, kind, payload in heap:
                if kind == "wake":
                    heap_enc.append((float(tt), int(sq), kind, None))
                elif kind == "fault":
                    heap_enc.append((float(tt), int(sq), kind,
                                     dict(payload)))
                else:
                    tid = payload["tid"]
                    if tid not in tasks_enc:
                        tasks_enc[tid] = self._encode_task(payload,
                                                           params_table)
                    heap_enc.append((float(tt), int(sq), kind, tid))
            snap = self._base_snapshot(fleet, global_params, hist)
            snap.update(
                state=dict(state),
                cohorts={int(k): dict(v) for k, v in cohorts.items()},
                last_done=dict(last_done),
                window_devices=list(window_devices),
                window_models=list(window_models),
                busy64=busy64.copy(),
                alive_host=alive_host.copy(),
                disconnected=sorted(int(x) for x in disconnected),
                corrupt_pending={int(k): [tuple(x) for x in v]
                                 for k, v in corrupt_pending.items()},
                tasks=tasks_enc,
                heap=heap_enc,
                params_table=params_table,
            )
            self.ckpt.save(snap, self._ckpt_meta(state["vround"]))
            self._after_save()

        last_ckpt = {"vround": state["vround"]}

        def maybe_checkpoint():
            if self.ckpt is None or self.ckpt_every <= 0:
                return
            v = state["vround"]
            if v > last_ckpt["vround"] and v % self.ckpt_every == 0:
                last_ckpt["vround"] = v
                save_checkpoint()

        # --- timeline -------------------------------------------------
        if rs is None:
            maybe_hotplug()  # hotplug_round == 0 joins before first dispatch
            refill()
            commit_ready()
        while True:
            if not heap:
                if not state["hotplug_done"] \
                        and state["tasks_started"] < budget:
                    # no event can ever advance the virtual-round counter
                    # to the join boundary (e.g. the whole initial fleet is
                    # too drained to take a task), but sync mode reaches it
                    # by ticking empty rounds — connect the joiners now so
                    # the two modes agree on the hot-plug story
                    maybe_hotplug(force=True)
                    refill()
                    commit_ready()
                    if heap:
                        continue
                if (gate_avail and state["tasks_started"] < budget
                        and not state["budget_blocked"]):
                    # the timeline starved only because every idle device is
                    # offline right now — wake at the next opening (diurnal
                    # dawn / carbon-window reopen) and dispatch again
                    idle_u = alive_host & (busy64 <= state["now"] + 1e-9)
                    if idle_u.any() and not (
                            scenario.available_host(tz_host, state["now"])
                            & idle_u).any():
                        t_wake = scenario.next_available_host(
                            tz_host[idle_u], state["now"])
                        if horizon <= 0 or t_wake < horizon - 1e-9:
                            heapq.heappush(heap, (float(t_wake),
                                                  state["seq"], "wake",
                                                  None))
                            state["seq"] += 1
                            continue
                break
            t_ev, _, kind, payload = heapq.heappop(heap)
            state["now"] = t_ev
            if kind == "done":
                # a task marked lost settles at its reap event instead
                if not payload.get("lost"):
                    process_completion(payload)
                if not reaping:
                    tasks.pop(payload["tid"], None)
            elif kind == "reap":
                # the reap event is always a task's LAST heap entry
                # (deadline > completion time), so release it here
                process_reap(payload)
                tasks.pop(payload["tid"], None)
            elif kind == "wake":
                pass            # availability wake: refill() below dispatches
            else:
                process_fault(payload)
            refill()
            commit_ready()
            maybe_checkpoint()

        if window_devices or state["window_lost"]:
            emit_row()
        # flush cohorts whose tasks were cut by the horizon/budget
        for c in cohorts.values():
            c["pending"] = 0
        commit_ready()

        if marl and buffer is not None and marl.ep_rewards:
            # event-driven runs have no natural mid-run barrier to train at
            # (the episode trace only fully commits once in-flight cohorts
            # land), so the learner trains at episode end with the same
            # total update count a sync run would have used
            n_updates = cfg.marl_updates_per_round * max(
                1, state["vround"] // max(1, cfg.marl_train_every))
            _marl_train(marl, buffer, hist, fleet, state["vround"],
                        n_updates)

        budget_kind = None
        if state["tasks_started"] >= budget:
            reason = "budget_exhausted"
            budget_kind = "tasks"
        elif not bool(alive_host.any()):
            # every device (including all in-flight work) died: nothing can
            # ever be dispatched again — the terminal marker tells callers
            # the run ended early rather than silently under-delivering
            reason = "fleet_dead"
        elif budget_active and state["budget_blocked"]:
            # global energy budget can no longer fund any dispatch
            reason = "budget_exhausted"
            budget_kind = "energy"
        elif horizon > 0:
            reason = "horizon_reached"
        else:
            reason = "starved"
        hist["terminated"] = {
            "reason": reason, "vrounds": state["vround"],
            "tasks_started": state["tasks_started"],
            "completions": state["completions"],
            "lost": hist["faults"]["n_reaped"],
            "sim_time": state["now"],
        }
        if budget_kind is not None:
            hist["terminated"]["budget"] = budget_kind
        hist["n_tasks"] = state["tasks_started"]
        hist["n_aggregations"] = state["version"]
        hist["sim_time_total"] = state["now"]
        hist["k_final"] = top_k()
        return self._finalize(hist, global_params)

    def _finalize(self, hist, global_params) -> Dict:
        self._flush_quarantine(hist)
        hist["final_acc"] = hist["acc"][-1] if hist["acc"] else np.zeros(4)
        hist["best_acc"] = (np.max(np.stack(hist["acc"]), axis=0)
                            if hist["acc"] else np.zeros(4))
        hist["params"] = global_params
        return hist
