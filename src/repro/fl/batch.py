"""Bucketed-vmap client-update executor: one jit dispatch per submodel bucket.

The per-client path (repro.fl.client) costs one jit dispatch per participant
per mini-batch — `participants x epochs x steps` program launches per round,
which dominates wall time at the 256-4096 fleet sizes of the Fig. 6
scalability study.  This module amortizes that to AT MOST ONE program
execution per populated submodel bucket (<= 4 per round, one per model
index):

1. bucket the cohort by submodel index ``m`` (shapes are static per index);
2. precompute a fixed-shape padded batch schedule per bucket on the host —
   per-client epoch permutations from the same ``client_update_seed`` RNG
   the per-client path uses, laid out as global-dataset gather indices
   ``[P, T, B]`` plus a ``[P, T]`` step-validity mask (pad steps re-run
   batch 0 of the schedule but are masked out of both the SGD update and
   the loss, so padding changes nothing);
3. run the bucket as ONE jit program: ``jax.vmap`` over participants of a
   ``jax.lax.scan`` over the T-step schedule, or, where the family says a
   client's step fills the device by itself, the participants one after
   another for their own steps only (pad steps and rows never run);
   either way gathering mini-batches device-side from the resident
   training set (no per-batch host->device copies, :func:`resident`) and
   accumulating losses on device (one host sync per bucket).

The executor returns STACKED deltas ``[P, ...]`` per bucket in the
submodel's own tree structure — exactly what the stacked layer-aligned
aggregation path (repro.fl.server.aggregate_drfl_stacked -> Pallas
``layer_agg``) consumes without unstacking.  Baseline methods (HeteroFL /
ScaleFL) unstack to per-client trees for their scatter aggregation.

Shape discipline: P is padded to the next power of two of the bucket's
clients and T to the next power of two of its longest schedule (where
clients run in turn, P is a fixed :data:`IN_TURN_ROWS` and T the fleet's
longest schedule, :func:`run_cohort`), so recurring rounds reuse the same
compiled programs; ``COUNTERS`` tracks logical compilations (new shape signatures)
and program executions for the dispatch-count regression guard in
``tests/test_batch.py``, and the step slots executed and the real
(unmasked) steps among them, so the padding share of the lanes is
``1 - real_steps / slots``.  Each bucket program runs inside a
``drfl.client_update`` span carrying the same counts (repro.telemetry).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.models.family import resolve_family

# dispatch accounting: "compiles" counts NEW (method, model, shape) program
# signatures, "executions" counts bucket program launches, "slots" the
# client steps those launches executed (P_pad x T_pad with lanes, the real
# ones with clients in turn), "skipped_slots" the schedule's slots that did
# not run and "real_steps" the unmasked ones.  The regression guard asserts
# <= n_buckets executions per sync round and a bounded compile count
# across a run.
COUNTERS = {"compiles": 0, "executions": 0, "slots": 0, "skipped_slots": 0,
            "real_steps": 0}

# rows of every bucket where clients run in turn: a fixed count keeps one
# bucket program per submodel, and a small one keeps the round's stacked
# outputs near its real clients (a pad row is a zero delta held on the
# device, though it never runs)
IN_TURN_ROWS = 4
_SEEN_SIGNATURES: set = set()


def reset_counters() -> None:
    for key in COUNTERS:
        COUNTERS[key] = 0
    _SEEN_SIGNATURES.clear()


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def submodel_params(method: str, global_params, model_idx: int,
                    family=None):
    """The initial tree every client in bucket ``model_idx`` trains."""
    return resolve_family(family).submodel_params(method, global_params,
                                                  model_idx)


# ---------------------------------------------------------------------------
# host-side schedule construction (RNG parity with data.loader.epoch_batches)
# ---------------------------------------------------------------------------


def client_schedule(part: np.ndarray, seed: int, epochs: int,
                    batch: int) -> np.ndarray:
    """Global-dataset gather indices ``[T_i, B]`` for one client's local run.

    Replicates :func:`repro.data.loader.epoch_batches` exactly — shuffled
    epochs, full batches only, one wrap-around padded batch for clients with
    fewer than ``batch`` samples — so a bucketed client consumes the same
    sample sequence as the per-client reference under the same seed."""
    rng = np.random.default_rng(seed)
    part = np.asarray(part)
    n = len(part)
    steps = []
    for _ in range(epochs):
        idx = rng.permutation(n)
        for i in range(0, n - batch + 1, batch):
            steps.append(part[idx[i:i + batch]])
        if n < batch:
            steps.append(part[np.resize(idx, batch)])
    return np.asarray(steps, np.int32).reshape(len(steps), batch)


@dataclasses.dataclass
class Bucket:
    """One submodel bucket's padded schedule (host arrays)."""
    model_idx: int
    participants: List[int]          # device ids, cohort order
    weights: List[float]             # data sizes, aligned with participants
    gather: np.ndarray               # [P_pad, T_pad, B] int32
    valid: np.ndarray                # [P_pad, T_pad] float32

    @property
    def n_real(self) -> int:
        return len(self.participants)


def longest_schedule(parts: Sequence[np.ndarray], *, epochs: int,
                     batch: int) -> int:
    """The longest local schedule (steps) of any client holding data in
    ``parts``, as :func:`client_schedule` lays it out."""
    return max((epochs * max(len(p) // batch, 1) for p in parts if len(p)),
               default=0)


def bucket_cohort(participants: Sequence[int], model_idxs: Sequence[int],
                  parts: Sequence[np.ndarray], seeds: Sequence[int],
                  weights: Sequence[float], *, epochs: int,
                  batch: int, t_pad: Optional[int] = None,
                  rows: Optional[int] = None) -> List[Bucket]:
    """Group a cohort by submodel index and build padded schedules.

    Each bucket's P and T are the next powers of two of its clients and of
    its longest client's schedule, or T is ``t_pad`` where that is larger.
    With ``rows``, a submodel's clients fill as many buckets of exactly
    ``rows`` rows as they need instead.  Zero-data participants must be
    filtered by the caller (they have no schedule; the engine already
    skips them)."""
    by_m: Dict[int, List[int]] = {}
    for j, m in enumerate(model_idxs):
        by_m.setdefault(int(m), []).append(j)
    groups = [(m, by_m[m][i:i + rows] if rows else by_m[m])
              for m in sorted(by_m)
              for i in range(0, len(by_m[m]), rows or len(by_m[m]))]
    buckets = []
    for m, js in groups:
        scheds = [client_schedule(parts[j], seeds[j], epochs, batch)
                  for j in js]
        t = max(_next_pow2(max(len(s) for s in scheds)), t_pad or 0)
        p = rows or _next_pow2(len(js))
        gather = np.zeros((p, t, batch), np.int32)
        valid = np.zeros((p, t), np.float32)
        for r, s in enumerate(scheds):
            gather[r, :len(s)] = s
            # pad steps replay the client's first batch (real rows, so the
            # compute stays finite) but are masked out of update + loss
            gather[r, len(s):] = s[0]
            valid[r, :len(s)] = 1.0
        gather[len(js):] = gather[0]     # pad clients replay client 0, masked
        buckets.append(Bucket(model_idx=m,
                              participants=[int(participants[j]) for j in js],
                              weights=[float(weights[j]) for j in js],
                              gather=gather, valid=valid))
    return buckets


# ---------------------------------------------------------------------------
# the bucket program: vmapped lanes of a masked scan, or clients in turn
# ---------------------------------------------------------------------------


def _scan_unroll() -> bool | int:
    # XLA CPU executes conv bodies inside while-loops (what lax.scan lowers
    # to) ~6-8x slower than the same ops at top level — the in-loop thunks
    # miss the fused/multithreaded Eigen paths.  Fully unrolling restores
    # full speed at the price of compile time linear in T (bounded by the
    # pow2 T padding).  TPU/GPU keep the rolled scan: it compiles in O(1)
    # and runs at full speed there.
    return True if jax.default_backend() == "cpu" else 1


def _lanes(loss_fn, sub_params, x_all, y_all, gather, valid, lr):
    """vmap over participants of a masked scan over the whole schedule."""
    def one_client(g_i, v_i):
        def body(carry, inp):
            params, loss_sum, n_valid = carry
            idx, v = inp
            xb = jnp.take(x_all, idx, axis=0)
            yb = jnp.take(y_all, idx, axis=0)
            loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
            # v==1.0 multiplies are exact, so real steps match the
            # per-client `p - lr*g`; v==0.0 makes the step an identity
            params = jax.tree.map(lambda p, g: p - lr * (g * v),
                                  params, grads)
            return (params, loss_sum + loss * v, n_valid + v), None

        (params, loss_sum, n_valid), _ = jax.lax.scan(
            body, (sub_params, jnp.float32(0.0), jnp.float32(0.0)),
            (g_i, v_i), unroll=_scan_unroll())
        delta = jax.tree.map(lambda a, b: a - b, params, sub_params)
        return delta, loss_sum / jnp.maximum(n_valid, 1.0)

    return jax.vmap(one_client)(gather, valid)


def _in_turn(family, loss_fn, sub_params, x_all, y_all, gather, valid, lr):
    """The participants one after another, each for its own steps only.

    A client's step count is the length of its mask's prefix of ones, so
    it is data and not a shape: pad steps and pad rows never run, and the
    compiled program is the one of the schedule's (P, T) alone.  Each
    client writes its delta and mean loss into its row of the one stacked
    output, which the loop carries and updates in place.  ``x_all`` is the
    training set as rows (:func:`resident`); the family turns a gathered
    batch of rows back into its samples."""
    p_pad = gather.shape[0]
    n_steps = jnp.sum(valid, axis=1)

    def one_client(i, out):
        deltas, losses = out
        g_i = jax.lax.dynamic_index_in_dim(gather, i, keepdims=False)
        v_i = jax.lax.dynamic_index_in_dim(valid, i, keepdims=False)

        def step(t, carry):
            params, loss_sum = carry
            idx = jax.lax.dynamic_index_in_dim(g_i, t, keepdims=False)
            # times the step's mask value, 1.0 on every step that runs (an
            # exact identity): with no loop-variant factor before it, XLA
            # TPU moves the batch's float32 -> bfloat16 conversion for the
            # convolutions onto the whole training set and hoists it out
            # of both loops, one more copy of the set (0.27 GiB at
            # CIFAR-10 size)
            xb = family.samples_from_rows(
                sub_params,
                jnp.take(x_all, idx, axis=0) * v_i[t].astype(x_all.dtype))
            yb = jnp.take(y_all, idx, axis=0)
            loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
            params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return params, loss_sum + loss

        n = n_steps[i]
        params, loss_sum = jax.lax.fori_loop(
            0, n.astype(jnp.int32), step, (sub_params, jnp.float32(0.0)))
        deltas = jax.tree.map(
            lambda d, a, b: jax.lax.dynamic_update_index_in_dim(
                d, a - b, i, 0), deltas, params, sub_params)
        losses = losses.at[i].set(loss_sum / jnp.maximum(n, 1.0))
        return deltas, losses

    out = (jax.tree.map(lambda a: jnp.zeros((p_pad,) + a.shape, a.dtype),
                        sub_params),
           jnp.zeros((p_pad,), jnp.float32))
    return jax.lax.fori_loop(0, p_pad, one_client, out)


@functools.partial(jax.jit, static_argnames=("method", "lr", "family"))
def _bucket_program(sub_params, x_all, y_all, gather, valid, *, method: str,
                    lr: float, family):
    """ONE program for a whole bucket.

    sub_params: the bucket's submodel tree (shared initial point)
    gather:     [P, T, B] int32 rows into x_all/y_all
    valid:      [P, T] float32 step mask, a prefix of ones per client
                (0 = padding, no-op step)

    Returns (stacked delta pytree [P, ...], mean losses [P]); pad rows'
    deltas and losses are exactly 0.  The family picks, at trace time,
    between clients in turn and vmapped lanes
    (``family.bucket_clients_in_turn``).
    """
    loss_fn = family.loss_fn(method)
    if family.bucket_clients_in_turn():
        return _in_turn(family, loss_fn, sub_params, x_all, y_all, gather,
                        valid, lr)
    # families may swap in a vmap-friendly forward for the batched trace
    # (the CNN's patches+einsum convs on CPU, where vmapped per-client
    # conv kernels lower to a pathological grouped-conv path)
    with family.bucket_trace_context():
        return _lanes(loss_fn, sub_params, x_all, y_all, gather, valid, lr)


def _signature(family, method: str, model_idx: int, sub_params,
               gather_shape, data_shape, lr: float):
    shapes = tuple((tuple(l.shape), str(l.dtype))
                   for l in jax.tree.leaves(sub_params))
    return (family.name, method, int(model_idx), tuple(gather_shape),
            tuple(data_shape), float(lr), shapes)


@dataclasses.dataclass
class BucketResult:
    """Stacked outcome of one bucket execution.

    ``stacked_delta`` keeps the executor's pow2 participant padding (pad
    rows carry weight 0.0, so downstream weighted aggregation ignores them
    exactly, and the stacked aggregation leaves them out of its stack).
    Real rows are the first ``len(participants)``."""
    model_idx: int
    participants: List[int]
    weights: List[float]             # [P_pad], 0.0 beyond the real rows
    stacked_delta: object            # submodel pytree, leaves [P_pad, ...]
    losses: np.ndarray               # [P_real] float


def run_bucket(method: str, global_params, x_all, y_all, bucket: Bucket, *,
               lr: float, family=None) -> BucketResult:
    """Execute one bucket as a single jit program."""
    fam = resolve_family(family)
    sub = fam.submodel_params(method, global_params, bucket.model_idx)
    sig = _signature(fam, method, bucket.model_idx, sub,
                     bucket.gather.shape, x_all.shape, lr)
    if sig not in _SEEN_SIGNATURES:
        _SEEN_SIGNATURES.add(sig)
        COUNTERS["compiles"] += 1
    p = bucket.n_real
    p_pad, t_pad = bucket.gather.shape[:2]
    real_steps = int(np.count_nonzero(bucket.valid))
    # clients in turn run their real steps only; lanes run every slot
    slots = real_steps if fam.bucket_clients_in_turn() else p_pad * t_pad
    COUNTERS["executions"] += 1
    COUNTERS["slots"] += slots
    COUNTERS["skipped_slots"] += p_pad * t_pad - slots
    COUNTERS["real_steps"] += real_steps
    with telemetry.span("client_update", real_steps=real_steps,
                        slots=slots):
        stacked, losses = _bucket_program(
            sub, x_all, y_all, jnp.asarray(bucket.gather),
            jnp.asarray(bucket.valid), method=method, lr=float(lr),
            family=fam)
        # jaxlint: allow(host-sync-in-hot-path) -- one losses pull per bucket program; deltas stay on device
        losses = np.asarray(losses[:p])
    return BucketResult(model_idx=bucket.model_idx,
                        participants=list(bucket.participants),
                        weights=(list(bucket.weights)
                                 + [0.0] * (p_pad - p)),
                        stacked_delta=stacked, losses=losses)


# ---------------------------------------------------------------------------
# cohort-level API used by the round engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CohortResult:
    buckets: List[BucketResult]

    def unstacked(self):
        """Per-participant (device_id, model_idx, delta, weight, loss),
        in bucket order — for the list-based aggregation paths."""
        out = []
        for b in self.buckets:
            for r, i in enumerate(b.participants):
                delta = jax.tree.map(lambda a, r=r: a[r], b.stacked_delta)
                # jaxlint: allow(host-sync-in-hot-path) -- BucketResult.losses is already host numpy (pulled once per bucket)
                loss = float(b.losses[r])
                out.append((i, b.model_idx, delta, b.weights[r], loss))
        return out


def resident(x, family=None):
    """The training set ``x`` (host array) as the bucket programs gather
    from it on the device.

    Where the family runs clients in turn, as rows ``[N, D]``, so that a
    batch is a gather of whole rows: a TPU keeps an ``[N, H, W, C]`` image
    set with N as its minor dimension, and XLA then makes a relaid-out
    copy of the whole set inside each bucket program for the per-step
    gather (1.1 GiB beside the set's 0.55 at CIFAR-10 size).  The bucket
    program turns each gathered batch back into samples
    (``family.samples_from_rows``)."""
    x = np.asarray(x)
    if resolve_family(family).bucket_clients_in_turn():
        x = x.reshape(len(x), -1)
    return jnp.asarray(x)


def run_cohort(method: str, global_params, x_all, y_all,
               parts: Sequence[np.ndarray], participants: Sequence[int],
               model_idxs: Sequence[int], seeds: Sequence[int],
               weights: Optional[Sequence[float]] = None, *, epochs: int,
               batch: int, lr: float, family=None,
               fleet_steps: Optional[int] = None) -> CohortResult:
    """Run a whole cohort's local training in <= n_buckets jit dispatches.

    ``parts`` is aligned with ``participants`` (one index array each);
    zero-data participants must already be filtered out.  Where the family
    runs clients in turn, pad steps and rows cost nothing, so every bucket
    is laid out at ``fleet_steps`` (:func:`longest_schedule` of the whole
    fleet, required there) and at :data:`IN_TURN_ROWS` rows: the bucket
    program's key is then its submodel alone, whichever clients the
    round chose and however long they train."""
    if weights is None:
        weights = [float(len(p)) for p in parts]
    fam = resolve_family(family)
    t_pad = rows = None
    if fam.bucket_clients_in_turn():
        if not fleet_steps:
            raise ValueError("clients in turn are laid out at the fleet's "
                             "longest schedule: pass fleet_steps")
        t_pad, rows = _next_pow2(fleet_steps), IN_TURN_ROWS
    with telemetry.span("bucket_schedule"):
        buckets = bucket_cohort(participants, model_idxs, parts, seeds,
                                weights, epochs=epochs, batch=batch,
                                t_pad=t_pad, rows=rows)
    x_all = jnp.asarray(x_all)
    y_all = jnp.asarray(y_all)
    return CohortResult(buckets=[
        run_bucket(method, global_params, x_all, y_all, b, lr=lr,
                   family=fam)
        for b in buckets])
