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
   ``jax.lax.scan`` over the T-step schedule, gathering mini-batches
   device-side from the resident training set (no per-batch host->device
   copies) and accumulating losses on device (one host sync per bucket).

The executor returns STACKED deltas ``[P, ...]`` per bucket in the
submodel's own tree structure — exactly what the stacked layer-aligned
aggregation path (repro.fl.server.aggregate_drfl_stacked -> Pallas
``layer_agg``) consumes without unstacking.  Baseline methods (HeteroFL /
ScaleFL) unstack to per-client trees for their scatter aggregation.

Shape discipline: P is padded to the next power of two and T to the next
power of two of the bucket's longest schedule, so recurring rounds reuse
the same compiled programs; ``COUNTERS`` tracks logical compilations (new
shape signatures) and program executions for the dispatch-count regression
guard in ``tests/test_batch.py``, and the step slots executed and the real
(unmasked) steps among them, so the padding share is
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
# P_pad x T_pad client steps those launches executed and "real_steps" the
# unmasked ones among them.  The regression guard asserts <= n_buckets
# executions per sync round and a bounded compile count across a run.
COUNTERS = {"compiles": 0, "executions": 0, "slots": 0, "real_steps": 0}
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


def bucket_cohort(participants: Sequence[int], model_idxs: Sequence[int],
                  parts: Sequence[np.ndarray], seeds: Sequence[int],
                  weights: Sequence[float], *, epochs: int,
                  batch: int) -> List[Bucket]:
    """Group a cohort by submodel index and build padded schedules.

    Zero-data participants must be filtered by the caller (they have no
    schedule; the engine already skips them)."""
    by_m: Dict[int, List[int]] = {}
    for j, m in enumerate(model_idxs):
        by_m.setdefault(int(m), []).append(j)
    buckets = []
    for m in sorted(by_m):
        js = by_m[m]
        scheds = [client_schedule(parts[j], seeds[j], epochs, batch)
                  for j in js]
        t_pad = _next_pow2(max(len(s) for s in scheds))
        p_pad = _next_pow2(len(js))
        gather = np.zeros((p_pad, t_pad, batch), np.int32)
        valid = np.zeros((p_pad, t_pad), np.float32)
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
# the bucket program: vmap over participants of a scan over the schedule
# ---------------------------------------------------------------------------


def _scan_unroll() -> bool | int:
    # XLA CPU executes conv bodies inside while-loops (what lax.scan lowers
    # to) ~6-8x slower than the same ops at top level — the in-loop thunks
    # miss the fused/multithreaded Eigen paths.  Fully unrolling restores
    # full speed at the price of compile time linear in T (bounded by the
    # pow2 T padding).  TPU/GPU keep the rolled scan: it compiles in O(1)
    # and runs at full speed there.
    return True if jax.default_backend() == "cpu" else 1


@functools.partial(jax.jit, static_argnames=("method", "lr", "family"))
def _bucket_program(sub_params, x_all, y_all, gather, valid, *, method: str,
                    lr: float, family):
    """ONE program for a whole bucket.

    sub_params: the bucket's submodel tree (shared initial point)
    gather:     [P, T, B] int32 rows into x_all/y_all
    valid:      [P, T] float32 step mask (0 = padding, no-op step)

    Returns (stacked delta pytree [P, ...], mean losses [P]).
    """
    loss_fn = family.loss_fn(method)

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

    # families may swap in a vmap-friendly forward for the batched trace
    # (the CNN's patches+einsum convs on CPU, where vmapped per-client
    # conv kernels lower to a pathological grouped-conv path)
    with family.bucket_trace_context():
        return jax.vmap(one_client)(gather, valid)


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
    rows carry garbage deltas and weight 0.0, so downstream weighted
    aggregation ignores them exactly) — stable shapes mean the stacked
    aggregation program compiles once per bucket signature.  Real rows are
    the first ``len(participants)``."""
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
    slots = p_pad * t_pad
    real_steps = int(np.count_nonzero(bucket.valid))
    COUNTERS["executions"] += 1
    COUNTERS["slots"] += slots
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


def run_cohort(method: str, global_params, x_all, y_all,
               parts: Sequence[np.ndarray], participants: Sequence[int],
               model_idxs: Sequence[int], seeds: Sequence[int],
               weights: Optional[Sequence[float]] = None, *, epochs: int,
               batch: int, lr: float, family=None) -> CohortResult:
    """Run a whole cohort's local training in <= n_buckets jit dispatches.

    ``parts`` is aligned with ``participants`` (one index array each);
    zero-data participants must already be filtered out."""
    if weights is None:
        weights = [float(len(p)) for p in parts]
    with telemetry.span("bucket_schedule"):
        buckets = bucket_cohort(participants, model_idxs, parts, seeds,
                                weights, epochs=epochs, batch=batch)
    x_all = jnp.asarray(x_all)
    y_all = jnp.asarray(y_all)
    fam = resolve_family(family)
    return CohortResult(buckets=[
        run_bucket(method, global_params, x_all, y_all, b, lr=lr,
                   family=fam)
        for b in buckets])
