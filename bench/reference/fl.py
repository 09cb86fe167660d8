"""Plain reference of a synchronous DR-FL round, for any configuration
whose reference module (``bench/configs/<config>.py``) supplies
``make_dataset``, ``init``, ``apply_all_exits``, ``num_submodels`` and
``energy_flops``.

It imports nothing of the system under test and takes nothing it has
made.  From the deployment seed it makes the data, the Dirichlet shards
and the device profiles, and from the run's seed the initial weights and
the clients' batch orders, with the same random streams the system draws
(numpy's default generator, ``jax.random`` keys, ``SeedSequence`` client
seeds).  From the run under test it takes only the selector's
decisions, round by round: which devices train and at which depth, as a
served model's check takes the served tokens.  It then follows DR-FL's
round as the paper states it:

1. Eq. 5/7 energy accounting: each chosen device pays
   ``p_train * samples / (compute / fraction) + p_com * 2 * bytes / bw``
   joules, in float64, and dies if its battery cannot cover that;
2. each surviving client with data runs plain SGD over its shuffled local
   epochs (full batches, one wrapped batch for shards under a batch),
   on the joint exit loss (deepest held exit weight 1, shallower 0.3);
3. layer-aligned aggregation: every group (stem, stage i, exit i) moves by
   ``server_lr`` times the data-size-weighted mean of the deltas of the
   clients that hold it;
4. evaluation: per-exit accuracy on the server's validation split.

It computes in the precision the configuration states (float32, with
matmuls and convolutions at the TPU's default precision, as the model's
reference module applies them).  Two controls compute the same rounds
in bfloat16, the next precision below: ``dtype=jnp.bfloat16`` holds
parameters, activations and updates in bfloat16 (small SGD steps round
away, so it also stands for a state left unchanged), and
``compute_dtype=jnp.bfloat16`` keeps float32 parameters and updates but
runs every forward and backward pass, evaluation included, in bfloat16
(mixed precision, the shortcut a faster program would take).
``half_batch=True`` plants a fault: each step averages over half its
batch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: joint exit loss weight of the shallower exits a client holds
SHALLOW_EXIT_WEIGHT = 0.3
#: device tiers: (share of the fleet, samples/s, train W, comm W)
DEVICE_TIERS = (("small", 0.4, 120.0, 4.0, 1.5),
                ("medium", 0.3, 300.0, 8.0, 2.0),
                ("large", 0.3, 700.0, 18.0, 2.5))
PROFILE_JITTER = 0.15
BATTERY_J = 7560.0
BANDWIDTH = 2.5e6
EVAL_BATCH = 250


def client_update_seed(base_seed, round_idx, device_idx) -> int:
    return int(np.random.SeedSequence(
        entropy=(int(base_seed), int(round_idx), int(device_idx))
    ).generate_state(1)[0])


def dirichlet_partition(labels, n_clients, alpha, seed, min_per_client=8):
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    for _ in range(100):
        parts = [[] for _ in range(n_clients)]
        for c in range(n_classes):
            idx = np.flatnonzero(labels == c)
            rng.shuffle(idx)
            props = rng.dirichlet(np.full(n_clients, alpha))
            cuts = (np.cumsum(props)[:-1] * len(idx)).astype(int)
            for i, chunk in enumerate(np.split(idx, cuts)):
                parts[i].extend(chunk.tolist())
        if min(len(p) for p in parts) >= min_per_client:
            break
    return [np.asarray(sorted(p), dtype=np.int64) for p in parts]


def device_profiles(n, seed):
    """(compute, p_train, p_com) float64 [n]: a tier per device, then a
    +-15% jitter of each of its three rates, device by device."""
    rng = np.random.default_rng(seed)
    names = [t[0] for t in DEVICE_TIERS]
    tiers = rng.choice(names, size=n, p=[t[1] for t in DEVICE_TIERS])
    base = {t[0]: t[2:] for t in DEVICE_TIERS}
    out = np.zeros((n, 3))
    for i, t in enumerate(tiers):
        for j, v in enumerate(base[str(t)]):
            out[i, j] = v * rng.uniform(1 - PROFILE_JITTER, 1 + PROFILE_JITTER)
    return out[:, 0], out[:, 1], out[:, 2]


def client_schedule(part, seed, epochs, batch):
    """Sample rows ``[T, batch]`` of one client's local run."""
    rng = np.random.default_rng(seed)
    n = len(part)
    steps = []
    for _ in range(epochs):
        idx = rng.permutation(n)
        for i in range(0, n - batch + 1, batch):
            steps.append(part[idx[i:i + batch]])
        if n < batch:
            steps.append(part[np.resize(idx, batch)])
    return np.asarray(steps, np.int64).reshape(len(steps), batch)


def submodel(tree, m):
    return {"stem": tree["stem"], "stages": tree["stages"][:m + 1],
            "exits": tree["exits"][:m + 1]}


def cross_entropy(logits, y):
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])


def joint_loss(apply, sub, xb, yb):
    """CE at the deepest held exit, plus 0.3 x CE at each shallower one,
    normalised by the weights' sum."""
    outs = apply(sub, xb)
    loss = cross_entropy(outs[-1], yb)
    for o in outs[:-1]:
        loss = loss + SHALLOW_EXIT_WEIGHT * cross_entropy(o, yb)
    return loss / (1.0 + SHALLOW_EXIT_WEIGHT * (len(outs) - 1))


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


@functools.partial(jax.jit,
                   static_argnames=("apply", "lr", "half", "compute"))
def _sgd_step(sub, x_all, y_all, rows, *, apply, lr, half, compute):
    """One plain SGD step on the rows ``rows`` of the training set (the
    first half of them under the half-batch fault), its forward and
    backward pass in ``compute``."""
    if half:
        rows = rows[:rows.shape[0] // 2]
    loss, grads = jax.value_and_grad(
        lambda p: joint_loss(apply, _cast(p, compute), x_all[rows],
                             y_all[rows]))(sub)
    return jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype),
                        sub, grads), loss


@functools.partial(jax.jit, static_argnames=("apply", "compute"))
def _predictions(params, x, *, apply, compute):
    """[exits, batch] predicted classes."""
    return jnp.stack([jnp.argmax(o, -1)
                      for o in apply(_cast(params, compute), x)])


@dataclasses.dataclass
class RoundRecord:
    """What one synchronous round produced, on the host."""
    losses: Dict[int, float]          # device -> mean local loss
    params: object                    # global params after aggregation
    accs: np.ndarray                  # per-exit accuracy after the round
    remaining: np.ndarray             # battery joules after the charge
    ok: np.ndarray                    # charged devices that survived
    faults: int = 0                   # invalid decisions (see run)
    steps: Dict[int, int] = dataclasses.field(
        default_factory=dict)         # device -> local SGD steps


@dataclasses.dataclass
class RunRecord:
    params0: object
    accs0: np.ndarray
    rounds: List[RoundRecord]


class Reference:
    def __init__(self, model, fl: dict, seed: int, deployment_seed: int, *,
                 dtype=jnp.float32, compute_dtype=None,
                 half_batch: bool = False):
        self.model, self.fl, self.seed = model, fl, int(seed)
        self.dtype, self.half_batch = dtype, half_batch
        self.compute = jnp.dtype(compute_dtype or dtype)
        dep = int(deployment_seed)
        x, y = model.make_dataset(fl["n_train"], fl["num_classes"],
                                  fl["hw"], fl["noise"], dep)
        n_val = max(64, int(fl["n_val_fraction"] * fl["n_train"]))
        self.x_val, self.y_val = x[:n_val], y[:n_val]
        x_tr, y_tr = x[n_val:], y[n_val:]
        self.parts = dirichlet_partition(y_tr, fl["n_devices"], fl["alpha"],
                                         dep)
        self.x_tr = self._inputs(x_tr)
        self.y_tr = jnp.asarray(y_tr)
        sizes = np.asarray([len(p) for p in self.parts], np.float64)
        compute, p_train, p_com = device_profiles(fl["n_devices"], dep)
        self.sizes = sizes
        # [n, M] joules of a round at each depth (Eq. 5/7)
        m_bytes, frac = self._cost_model()
        t_tra = (sizes * fl["local_epochs"])[:, None] / (
            compute[:, None] / np.maximum(frac, 1e-6)[None, :])
        t_com = 2.0 * m_bytes[None, :] / BANDWIDTH
        self.need = p_train[:, None] * t_tra + p_com[:, None] * t_com
        self.remaining = np.full(fl["n_devices"], BATTERY_J
                                 * fl.get("energy_scale", 1.0))
        self.alive = np.ones(fl["n_devices"], bool)
        params = model.init(jax.random.PRNGKey(self.seed), fl["num_classes"],
                            fl["width_mult"])
        self.params = jax.tree.map(lambda a: a.astype(dtype), params)

    def _inputs(self, x):
        """Images in the dtype the passes compute in; token ids stay
        integers."""
        x = jnp.asarray(x)
        return x.astype(self.compute) if jnp.issubdtype(
            x.dtype, jnp.floating) else x

    # -- energy ---------------------------------------------------------------

    def _cost_model(self):
        """Submodel bytes (stem, stages <= m, exit m) of the published-width
        model, and each depth's share of the full model's FLOPs."""
        fl, model = self.fl, self.model
        shapes = jax.eval_shape(
            lambda k: model.init(k, fl["num_classes"], 1.0),
            jax.random.PRNGKey(0))
        M = model.num_submodels()
        nbytes = []
        for m in range(M):
            tree = {"stem": shapes["stem"], "stages": shapes["stages"][:m + 1],
                    "exits": [shapes["exits"][m]]}
            nbytes.append(sum(l.size * l.dtype.itemsize
                              for l in jax.tree.leaves(tree)))
        full = model.energy_flops(M - 1)
        frac = [model.energy_flops(m) / full for m in range(M)]
        return np.asarray(nbytes, np.float64), np.asarray(frac, np.float64)

    def charge(self, model_choice):
        choice = np.asarray(model_choice)
        active = (choice >= 0) & self.alive
        need = self.need[np.arange(len(choice)), np.clip(choice, 0, None)]
        if self.dtype != jnp.float32:
            need = np.asarray(jnp.asarray(need, self.dtype), np.float64)
        ok = active & (self.remaining > need)
        died = active & ~ok
        self.remaining = np.where(ok, self.remaining - need,
                                  np.where(died, 0.0, self.remaining))
        self.alive &= ~died
        return ok

    def affordable(self, device, m) -> bool:
        return bool(self.alive[device]
                    and self.need[device, m] < self.remaining[device])

    # -- training -------------------------------------------------------------

    def train_client(self, params, m, device, round_idx):
        sched = client_schedule(
            self.parts[device],
            client_update_seed(self.seed, round_idx, device),
            self.fl["local_epochs"], self.fl["batch_size"])
        sub0 = submodel(params, m)
        sub, losses = sub0, []
        for rows in sched:
            sub, loss = _sgd_step(sub, self.x_tr, self.y_tr,
                                  jnp.asarray(rows, jnp.int32),
                                  apply=self.model.apply_all_exits,
                                  lr=float(self.fl["lr"]),
                                  half=self.half_batch, compute=self.compute)
            losses.append(loss)
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), sub, sub0)
        return delta, float(np.mean(np.asarray(
            jax.device_get(losses), np.float64))), len(sched)

    def aggregate(self, params, contribs):
        """``contribs``: [(m, delta of submodel m, weight)]."""
        lr = self.fl["server_lr"]
        out = {"stem": params["stem"], "stages": list(params["stages"]),
               "exits": list(params["exits"])}

        def mean_of(trees_w):
            tot = sum(w for _, w in trees_w)
            return jax.tree.map(
                lambda *ls: sum(w * l for l, (_, w) in zip(ls, trees_w)) / tot,
                *[t for t, _ in trees_w])

        def move(old, avg):
            return jax.tree.map(
                lambda p, d: (p.astype(jnp.float32) + lr * d).astype(p.dtype),
                old, avg)

        out["stem"] = move(params["stem"],
                           mean_of([(d["stem"], w) for _, d, w in contribs]))
        for i in range(len(params["stages"])):
            held = [(m, d, w) for m, d, w in contribs if m >= i]
            if not held:
                continue
            out["stages"][i] = move(params["stages"][i], mean_of(
                [(d["stages"][i], w) for _, d, w in held]))
            out["exits"][i] = move(params["exits"][i], mean_of(
                [(d["exits"][i], w) for _, d, w in held]))
        return out

    def evaluate(self, params):
        hits = None
        for i in range(0, len(self.x_val), EVAL_BATCH):
            xb = self._inputs(self.x_val[i:i + EVAL_BATCH])
            preds = np.asarray(_predictions(params, xb,
                                            apply=self.model.apply_all_exits,
                                            compute=self.compute))
            h = (preds == self.y_val[i:i + EVAL_BATCH][None]).sum(axis=1)
            hits = h if hits is None else hits + h
        return hits / len(self.x_val)

    # -- rounds ---------------------------------------------------------------

    def faults(self, participants, model_choice, k) -> int:
        """Decisions no valid selector makes, against this reference's
        batteries before the charge: more than ``k`` devices, a device
        twice, a depth out of range, a dead device or one that cannot
        afford its depth, a depth given to a device not chosen."""
        n, M = len(model_choice), self.model.num_submodels()
        bad = max(0, len(participants) - k)
        bad += len(participants) - len(set(participants))
        for i in participants:
            m = model_choice[i] if 0 <= i < n else -1
            bad += not (0 <= m < M and self.affordable(i, m))
        chosen = set(participants)
        bad += sum(1 for i, m in enumerate(model_choice)
                   if m >= 0 and i not in chosen)
        return int(bad)

    def run(self, decisions) -> RunRecord:
        """``decisions``: per round, ``(participants, model_choice, k)`` as
        the selector under test made them."""
        params = self.params
        record = RunRecord(params0=jax.device_get(params),
                           accs0=self.evaluate(params), rounds=[])
        for t, (participants, model_choice, k) in enumerate(decisions):
            faults = self.faults(participants, model_choice, k)
            ok = self.charge(model_choice)
            contribs, losses, steps = [], {}, {}
            for i in participants:
                if not ok[i] or len(self.parts[i]) == 0:
                    continue
                m = int(model_choice[i])
                delta, loss, steps[int(i)] = self.train_client(params, m, i, t)
                contribs.append((m, delta, float(len(self.parts[i]))))
                losses[int(i)] = loss
            if contribs:
                params = self.aggregate(params, contribs)
            record.rounds.append(RoundRecord(
                losses=losses, steps=steps, params=jax.device_get(params),
                accs=self.evaluate(params), remaining=self.remaining.copy(),
                ok=ok.copy(), faults=faults))
        return record
