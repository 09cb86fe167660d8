"""DR-FL federated simulation (paper §4.2 workflow, Steps 1–5).

One ``run_simulation`` call reproduces one cell of the paper's experiments:
a fleet of heterogeneous battery-powered devices trains a shared layer-wise
global model under an energy budget, with the configured dual-selection
strategy.  Returns a full history for the benchmark harnesses (accuracy per
exit per round, remaining energy, running time, fleet survival).

Rounds are scheduled by the event-driven :class:`repro.fl.engine.RoundEngine`:

* ``engine_mode="sync"`` (default) — classic barrier rounds, bit-for-bit
  identical to the frozen reference loop kept below
  (:func:`_run_once_reference`, the parity contract enforced by
  ``tests/test_engine.py``);
* ``engine_mode="async"`` — dispatch and completion are separate timeline
  events over per-device virtual clocks; late updates are aggregated with
  FedAsync-style staleness decay.  The default for Fig. 6 scalability runs.

Method arms:
    method="drfl"      selector in {marl, greedy, random, static}
    method="heterofl"  (greedy energy-aware model choice per the paper's
                        fair-comparison adaptation)
    method="scalefl"   (same greedy adaptation)
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core.fleet import (fleet_charge_jit, fleet_connect,
                              fleet_cost_matrix_jit, fleet_total_remaining)
from repro.core.selection import (GreedySelector, MarlSelector, RandomSelector,
                                  SelectorBase, StaticTierSelector)
from repro.fl import client as fl_client
from repro.fl import server as fl_server
from repro.fl.engine import RoundEngine, build_world, sync_task_budget
from repro.models.family import get_family


@dataclasses.dataclass
class FLConfig:
    n_devices: int = 40
    n_rounds: int = 30
    participation: float = 0.10         # paper: 10% per round
    local_epochs: int = 5               # paper §5
    batch_size: int = 32                # paper §5
    lr: float = 0.05                    # paper §5
    alpha: float = 0.5                  # Dirichlet non-IID
    num_classes: int = 10
    n_train: int = 4000
    n_val_fraction: float = 0.04        # paper Table 2 optimum
    noise: float = 1.0
    hw: int = 16                        # image size (CPU budget: 16x16)
    width_mult: float = 0.25            # CNN slimming for CPU-budget runs
    seed: int = 0
    model_family: str = "cnn"           # registered ModelFamily (see
                                        # repro.models.family / fl/spec.py)
    method: str = "drfl"                # drfl | heterofl | scalefl
    selector: str = "marl"              # marl | greedy | random | static
    reward_weights: tuple = (1000.0, 0.01, 1.0)
    marl_train_every: int = 2
    marl_updates_per_round: int = 2
    marl_episodes: int = 1              # selector pre-training episodes (the
                                        # reported run is the LAST episode)
    hotplug_round: int = 0              # paper §4.2: hot-plug devices join at
    hotplug_n: int = 0                  # this round with fresh batteries
    energy_scale: float = 1.0           # scales battery to stress budgets
    # --- energy scenarios (repro.energy; docs/ENERGY.md) -------------------
    # pluggable harvesting/availability profiles + a fleet-wide joule
    # budget; the defaults below are the trivial scenario, bit-for-bit
    # identical to profile-free runs
    charge_profile: str = "constant"    # constant | solar | carbon_window
    charge_rate: float = 0.0            # fleet-mean harvest amplitude, J/s
    charge_period: float = 86400.0      # profile day length, sim-seconds
    availability_profile: str = "always"  # always | diurnal
    availability_duty: float = 1.0      # fraction of the local day online
    global_budget_j: float = 0.0        # fleet-wide joule budget (0 = off)
    server_lr: float = 0.7              # damps layer-aligned update drift
    # --- event-driven round engine (repro.fl.engine) -----------------------
    engine_mode: str = "sync"           # sync | async
    staleness_decay: float = 0.5        # FedAsync (1+s)^-decay down-weighting
    async_eval_every: int = 1           # evaluate every N async aggregations
    async_time_horizon: float = 0.0     # sim-seconds budget (0 = task budget)
    async_task_budget: int = 0          # client tasks (0 = sync-equivalent)
    # --- client-update executor (repro.fl.batch) ---------------------------
    # "auto" buckets participants by submodel index and runs each bucket as
    # ONE vmap(scan) jit program at 64+ device fleets (<= 4 dispatches per
    # sync round); "perclient" keeps the bit-for-bit legacy per-client loop
    client_executor: str = "auto"       # auto | perclient | batched
    # --- scaled MARL state + fleet sharding --------------------------------
    # QMIX mixer global state: "flat" = the n_devices*OBS_DIM concatenation
    # (bit-for-bit legacy), "factored" = the fixed-width fleet summary whose
    # state_dim is independent of fleet size; "auto" keeps flat up to
    # repro.core.selection.FACTORED_AUTO_N (256) devices, factors above
    state_mode: str = "auto"            # auto | flat | factored
    # QMIX mixer: "flat" = per-agent hypernet (bit-for-bit legacy, O(n)
    # params + replay), "set" = permutation-invariant set/attention mixer
    # over sampled-agent replay (n-free training cost); "auto" keeps flat
    # up to FACTORED_AUTO_N (256) devices like state_mode
    mixer_mode: str = "auto"            # auto | flat | set
    # sampled-agent budget under the set mixer: episode traces and replay
    # minibatches keep at most this many agents (uniform per episode)
    marl_agent_budget: int = 4096
    # shard FleetState's [n] arrays over a jax.sharding "fleet" mesh of this
    # many local devices (0/1 = off, -1 = all local devices); selection +
    # energy kernels then run data-parallel (repro.sharding.fleet)
    fleet_mesh: int = 0
    # --- crash safety: checkpoint/resume + fault injection -----------------
    # (repro.checkpoint.engine + repro.fl.faults; docs/RESILIENCE.md)
    checkpoint_dir: str = ""            # empty = checkpointing off
    checkpoint_every: int = 0           # save every N (virtual) rounds
    checkpoint_keep: int = 3            # manifests kept (older ones rotate)
    resume: bool = False                # resume from latest manifest in dir
    fault_crashes: int = 0              # seeded churn counts (async only)
    fault_timeouts: int = 0
    fault_disconnects: int = 0
    fault_corrupts: int = 0
    fault_horizon: float = 0.0          # event window (0 = async horizon)
    fault_seed: int = -1                # -1 = reuse cfg.seed
    # in-flight tasks are declared lost (and their slot reclaimed) at
    # dispatch + factor * t_cost; only active when faults are injected
    task_deadline_factor: float = 4.0


def _make_selector(cfg: FLConfig, n_models: int) -> SelectorBase:
    if cfg.method in ("heterofl", "scalefl"):
        return GreedySelector()          # the paper's fair-comparison arm
    return {
        "marl": lambda: MarlSelector(
            cfg.n_devices + cfg.hotplug_n, n_models, cfg.n_rounds, cfg.seed,
            state_mode=getattr(cfg, "state_mode", "auto"),
            mixer_mode=getattr(cfg, "mixer_mode", "auto"),
            agent_budget=getattr(cfg, "marl_agent_budget", 4096)),
        "greedy": lambda: GreedySelector(),
        "random": lambda: RandomSelector(cfg.seed),
        "static": lambda: StaticTierSelector(cfg.seed),
    }[cfg.selector]()


# replay-buffer obs storage budget (float32 elements).  Episode obs are
# inherently [T+1, n, OBS_DIM], so at 4096+ devices a fixed 64-episode
# capacity is multi-GB before the first round runs — the "flat QMIX state
# OOM-scales" half of the Fig. 6 failure.  Capacity degrades gracefully
# instead (64 episodes at paper scale, >= 4 always).
_BUFFER_OBS_ELEMS = 2 ** 24


def _make_buffer(cfg: FLConfig):
    import logging

    from repro.core.marl.buffer import ReplayBuffer
    from repro.core.selection import OBS_DIM, marl_state_dim, resolve_mixer_mode
    from repro.models.family import get_family
    n_agents = cfg.n_devices + cfg.hotplug_n
    if cfg.engine_mode == "async":
        # one episode step per selector.select call: at most one per task
        # plus one failed-dispatch probe per completion/boundary event —
        # sized from the budget the engine will ACTUALLY dispatch
        budget = int(cfg.async_task_budget or sync_task_budget(cfg))
        episode_len = 2 * budget + cfg.n_rounds + 8
    else:
        episode_len = cfg.n_rounds
    state_dim = marl_state_dim(
        getattr(cfg, "state_mode", "auto"), n_agents,
        get_family(cfg.model_family).num_submodels())
    mixer_mode = resolve_mixer_mode(getattr(cfg, "mixer_mode", "auto"),
                                    n_agents)
    agent_budget = (int(getattr(cfg, "marl_agent_budget", 4096))
                    if mixer_mode == "set" else None)
    stored_agents = (min(n_agents, agent_budget) if agent_budget
                     else n_agents)
    capacity = max(4, min(64, _BUFFER_OBS_ELEMS
                          // ((episode_len + 1) * stored_agents * OBS_DIM)))
    if capacity < 64:
        # loud, once per buffer: fig5/table1 runs at scale must be able to
        # report their EFFECTIVE replay size (also recorded per-update in
        # hist["qmix"] by the engine)
        logging.getLogger(__name__).warning(
            "QMIX replay capacity degraded to %d episodes (episode_len=%d, "
            "stored agents=%d of %d, obs budget=%d elems); consider "
            "mixer_mode='set' / a smaller marl_agent_budget",
            capacity, episode_len, stored_agents, n_agents,
            _BUFFER_OBS_ELEMS)
    return ReplayBuffer(capacity, episode_len, n_agents, OBS_DIM,
                        state_dim, cfg.seed, agent_budget=agent_budget)


def run_simulation(cfg, verbose: bool = False,
                   halt_after_saves: int = 0) -> Dict:
    """Runs the FL simulation.  ``cfg`` is an :class:`FLConfig` (the stable
    flat compatibility surface) or a typed :class:`repro.fl.spec.
    SimulationSpec`; both are validated up front, so a typo like
    ``selector="mral"`` or ``engine_mode="asynch"`` raises here instead of
    deep inside a run.  With ``marl_episodes > 1`` and the MARL selector,
    earlier episodes pre-train the QMIX policy (fresh fleet + global model
    each episode, persistent learner + replay buffer) and the LAST episode
    is reported — the CPU-scale analogue of the paper's long online
    runs.

    Crash safety: with ``cfg.checkpoint_dir`` + ``cfg.checkpoint_every``
    set, the engine snapshots its FULL run state on that cadence; with
    ``cfg.resume=True`` the latest manifest in the directory is loaded
    (after a config-fingerprint check) and the run continues — histories
    and final params are byte-identical to an uninterrupted run.
    ``halt_after_saves=N`` (> 0, test/bench hook) simulates a crash by
    raising :class:`repro.checkpoint.engine.CheckpointHalt` right after
    the N-th checkpoint save of this call."""
    from repro.fl.spec import ensure_flat_config
    cfg = ensure_flat_config(cfg)
    resume_state = resume_meta = None
    if cfg.resume:
        from repro.checkpoint.engine import (EngineCheckpointer,
                                             config_fingerprint)
        if not cfg.checkpoint_dir:
            raise ValueError("resume=True needs checkpoint_dir")
        ck = EngineCheckpointer(cfg.checkpoint_dir,
                                keep=cfg.checkpoint_keep)
        latest = ck.latest()
        if latest is not None:
            resume_state, resume_meta = ck.load(latest)
            fp = config_fingerprint(cfg)
            got = resume_meta.get("fingerprint")
            if got != fp:
                raise ValueError(
                    f"checkpoint fingerprint {got!r} does not match this "
                    f"config ({fp!r}); refusing to resume a different run")
    halt = ({"remaining": int(halt_after_saves)} if halt_after_saves > 0
            else None)
    start_ep = int(resume_meta["episode"]) if resume_meta else 0
    selector = None
    buffer = None
    episodes = cfg.marl_episodes if (cfg.method == "drfl"
                                     and cfg.selector == "marl") else 1
    for ep in range(episodes):
        if ep < start_ep:
            # fully covered by the checkpoint: the restored selector +
            # buffer state already contain these episodes' training
            continue
        if selector is None:
            selector = _make_selector(
                cfg, get_family(cfg.model_family).num_submodels())
        marl = selector if isinstance(selector, MarlSelector) else None
        resuming = resume_state is not None and ep == start_ep
        if marl:
            if buffer is None:
                buffer = _make_buffer(cfg)
            if not resuming:
                # the resumed episode's trace/hidden/RNG state comes from
                # the checkpoint — resetting would fork the episode
                marl.reset_episode()
        engine = RoundEngine(cfg, selector, buffer,
                             verbose=verbose and ep == episodes - 1,
                             episode=ep,
                             resume_state=resume_state if resuming else None,
                             halt_counter=halt)
        hist = engine.run()
        resume_state = None              # consumed by its episode
    return hist


# ---------------------------------------------------------------------------
# frozen synchronous reference loop
# ---------------------------------------------------------------------------
#
# This is the pre-engine round loop, kept VERBATIM (modulo the shared
# build_world setup, the collision-free client seeds, and the family=
# routing that keeps it runnable on any registered model family) as the
# parity contract for RoundEngine's sync mode — the same role the scalar
# DeviceState path in repro.core.energy plays for the vectorized FleetState
# kernels.  tests/test_engine.py asserts engine sync histories match this
# bit-for-bit; do not "improve" it.


def _run_once_reference(cfg: FLConfig, verbose=False, selector=None,
                        buffer=None):
    w = build_world(cfg)
    fleet = w.fleet
    global_params = w.global_params
    M = w.n_models
    x_tr, y_tr, x_val, y_val, parts = w.x_tr, w.y_tr, w.x_val, w.y_val, w.parts
    sizes, fractions = w.sizes, w.fractions
    n_total = w.n_total
    if selector is None:
        selector = _make_selector(cfg, M)
    hist_hotplug_done = False

    marl = selector if isinstance(selector, MarlSelector) else None
    if marl:
        if buffer is None:
            from repro.core.marl.buffer import ReplayBuffer
            from repro.core.selection import OBS_DIM
            # state rows must match what THIS selector's episode_arrays
            # emits — its learner already resolved the state mode (flat
            # keeps the legacy n*OBS_DIM width bit-for-bit)
            buffer = ReplayBuffer(64, cfg.n_rounds, cfg.n_devices, OBS_DIM,
                                  marl.learner.cfg.state_dim, cfg.seed)
        marl.reset_episode()

    hist = {"acc": [], "acc_mean": [], "energy": [], "round_time": [],
            "alive": [], "participants": [], "model_choices": [],
            "reward": [], "dropouts": 0}
    prev_acc = float(np.mean(fl_server.evaluate(global_params, x_val, y_val,
                                                family=w.family)))
    e_prev = fleet_total_remaining(fleet)
    w1, w2, w3 = cfg.reward_weights
    rows = np.arange(n_total)

    for t in range(cfg.n_rounds):
        if (cfg.hotplug_n and not hist_hotplug_done
                and t >= cfg.hotplug_round):
            fleet = fleet_connect(fleet, cfg.n_devices, cfg.energy_scale)
            hist_hotplug_done = True
        n_connected = cfg.n_devices + (cfg.hotplug_n if hist_hotplug_done
                                       else 0)
        k = max(1, int(round(cfg.participation * n_connected)))
        sel = selector.select(fleet, t, k, sizes, fractions,
                              cfg.local_epochs, cfg.batch_size)

        choice = np.asarray(sel.model_choice, np.int64)
        active = choice >= 0
        m_idx = np.clip(choice, 0, M - 1)
        t_tra_m, t_com_m, e_tra_m, e_com_m = fleet_cost_matrix_jit(
            fleet, sizes, fractions, cfg.local_epochs, cfg.batch_size)
        need = np.asarray(e_tra_m + e_com_m)[rows, m_idx]
        t_cost = np.asarray(t_tra_m + t_com_m)[rows, m_idx]
        fleet, ok = fleet_charge_jit(fleet, jnp.asarray(need),
                                     jnp.asarray(active))
        ok = np.asarray(ok)
        hist["dropouts"] += int((active & ~ok).sum())
        survivors = active & ok
        t_round = float(t_cost[survivors].max()) if survivors.any() else 0.0

        deltas, idxs, weights = [], [], []
        for i in sel.participants:
            if not survivors[i]:
                continue                     # wasted energy, no contribution
            m = int(choice[i])
            xi = x_tr[parts[i]]
            yi = y_tr[parts[i]]
            if len(xi) == 0:
                continue
            upd_seed = fl_client.client_update_seed(cfg.seed, t, i)
            if cfg.method == "drfl":
                d_, _ = fl_client.drfl_client_update(
                    global_params, m, xi, yi, epochs=cfg.local_epochs,
                    batch=cfg.batch_size, lr=cfg.lr, seed=upd_seed,
                    family=w.family)
            elif cfg.method == "heterofl":
                d_, _ = fl_client.heterofl_client_update(
                    global_params, m, xi, yi, epochs=cfg.local_epochs,
                    batch=cfg.batch_size, lr=cfg.lr, seed=upd_seed,
                    family=w.family)
            else:
                d_, _ = fl_client.scalefl_client_update(
                    global_params, m, xi, yi, epochs=cfg.local_epochs,
                    batch=cfg.batch_size, lr=cfg.lr, seed=upd_seed,
                    family=w.family)
            deltas.append(d_)
            idxs.append(m)
            weights.append(float(len(xi)))

        if deltas:
            if cfg.method == "drfl":
                global_params = fl_server.aggregate_drfl(
                    global_params, deltas, idxs, weights,
                    server_lr=cfg.server_lr, family=w.family)
            else:
                global_params = fl_server.aggregate_sliced(
                    global_params, deltas, weights)

        accs = fl_server.evaluate(global_params, x_val, y_val,
                                  family=w.family)
        acc = float(np.mean(accs))
        e_now = fleet_total_remaining(fleet)
        reward = (w1 * (acc - prev_acc) - w2 * (e_prev - e_now)
                  - w3 * (t_round / 60.0))
        selector.observe_reward(reward)
        prev_acc, e_prev = acc, e_now

        if marl:
            if (t + 1) % cfg.marl_train_every == 0 and marl.ep_rewards:
                obs, state, actions, rewards = marl.episode_arrays(fleet, t + 1)
                buffer.add_episode(obs, state, actions, rewards)
                for _ in range(cfg.marl_updates_per_round):
                    batch = buffer.sample(marl.learner.cfg.batch_size)
                    if batch:
                        marl.learner.update(batch)

        alive_now = int(np.asarray(fleet.alive).sum())
        hist["acc"].append(np.asarray(accs))
        hist["acc_mean"].append(acc)
        hist["energy"].append(e_now)
        hist["round_time"].append(t_round)
        hist["alive"].append(alive_now)
        hist["participants"].append(list(sel.participants))
        hist["model_choices"].append([sel.model_choice[i] for i in sel.participants])
        hist["reward"].append(reward)
        if verbose:
            print(f"  round {t:3d}: acc={acc:.3f} exits="
                  f"{np.round(np.asarray(accs), 3)} alive={alive_now}"
                  f" energy={e_now:,.0f}J time={t_round:.1f}s r={reward:+.2f}")
        if alive_now == 0:
            break

    hist["final_acc"] = hist["acc"][-1] if hist["acc"] else np.zeros(4)
    hist["best_acc"] = (np.max(np.stack(hist["acc"]), axis=0)
                        if hist["acc"] else np.zeros(4))
    hist["params"] = global_params
    return hist, selector, buffer
