"""The comparison that decides ``correct``.

Both sides are a :class:`reference.fl.RunRecord`: the run under test
(built from what the window recorded) and the plain reference, which
followed the same selector decisions from the same seed.  The numbers,
each held against its limit in ``bench/limits/<workload>.json``:

* ``loss_gap``: the largest relative gap of a client's mean local loss;
* ``short_loss_gap``: the same for the first round's client with the
  fewest local steps (the reference's count), whose loss SGD has had the
  least time to amplify rounding in;
* ``update_gap``: the first round's global update (what the server's
  optimiser gets), leaf by leaf: the gap between the two sides' norms of
  a leaf's change, over the reference's norm of that leaf's change or of
  the median leaf's, whichever is larger; the worst leaf;
* ``change_gap``: the same for the parameters' change over all checked
  rounds;
* ``eval_gap``: the largest gap of a per-exit validation accuracy of the
  initial weights (both sides hold the same weights there, so this checks
  the evaluation itself);
* ``acc_gap``: the largest gap of a per-exit validation accuracy, at the
  start and after every checked round;
* ``energy_gap``: the largest gap of a device's remaining joules, over the
  full battery;
* ``selection_faults``: chosen devices that were dead, could not afford
  their depth, repeated, or out of range, more than ``k`` chosen, and
  devices whose survival of the charge differs from the reference's.

Leaves whose change in the reference is under a thousandth of the median
moved leaf's change move by round-off alone (exits no client held, for
one) and are left out of the two norm gaps.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "short_loss_gap", "update_gap", "change_gap",
           "eval_gap", "acc_gap", "energy_gap", "selection_faults")
#: a leaf counts when its reference change is at least this share of the
#: median leaf's
LEAF_FLOOR = 1e-3


def _leaf_norms(before, after):
    import jax
    a = jax.tree.leaves(before)
    b = jax.tree.leaves(after)
    return np.asarray([np.linalg.norm(np.asarray(y, np.float64)
                                      - np.asarray(x, np.float64))
                       for x, y in zip(a, b)])


def norm_gap(run_before, run_after, ref_before, ref_after):
    got = _leaf_norms(run_before, run_after)
    want = _leaf_norms(ref_before, ref_after)
    if got.shape != want.shape:
        return float("inf")
    moved = want[want > 0]
    if not moved.size:
        return 0.0 if not got.any() else float("inf")
    med = float(np.median(moved))
    keep = want >= LEAF_FLOOR * med
    gap = np.abs(got - want) / np.maximum(want, med)
    return float(gap[keep].max())


def compare(run, ref, battery_j) -> dict:
    """Numbers of ``run`` against ``ref`` (both RunRecords)."""
    def gap(got, want):
        return abs(got - want) / max(abs(want), 1e-12)

    losses = []
    for a, b in zip(run.rounds, ref.rounds):
        if set(a.losses) != set(b.losses):
            losses.append(float("inf"))
            continue
        losses += [gap(a.losses[dev], want) for dev, want in b.losses.items()]
    first = ref.rounds[0] if ref.rounds else None
    short = 0.0
    if first is not None and first.steps:
        dev = min(first.steps, key=first.steps.get)
        got = run.rounds[0].losses.get(dev) if run.rounds else None
        short = float("inf") if got is None else gap(got, first.losses[dev])
    eval_gap = float(np.max(np.abs(np.asarray(run.accs0)
                                   - np.asarray(ref.accs0))))
    accs = [eval_gap]
    accs += [np.max(np.abs(np.asarray(a.accs) - np.asarray(b.accs)))
             for a, b in zip(run.rounds, ref.rounds)]
    energy = [np.max(np.abs(np.asarray(a.remaining, np.float64)
                            - b.remaining)) / battery_j
              for a, b in zip(run.rounds, ref.rounds)]
    n = min(len(run.rounds), len(ref.rounds))
    return {
        "loss_gap": max(losses) if losses else 0.0,
        "short_loss_gap": short,
        "update_gap": norm_gap(run.params0, run.rounds[0].params,
                               ref.params0, ref.rounds[0].params),
        "change_gap": norm_gap(run.params0, run.rounds[n - 1].params,
                               ref.params0, ref.rounds[n - 1].params),
        "eval_gap": eval_gap,
        "acc_gap": float(max(accs)),
        "energy_gap": float(max(energy)) if energy else 0.0,
        "selection_faults": float(
            sum(b.faults for b in ref.rounds)
            + sum(int(np.sum(np.asarray(a.ok, bool) != np.asarray(b.ok)))
                  for a, b in zip(run.rounds, ref.rounds))),
    }


def verdict(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]) — every number the limits file
    holds at or under its limit, and finite.  A number with no limit is
    not compared (see ``bench/limits/<workload>.json`` and PERF.md)."""
    rows, ok = [], True
    for name in NUMBERS:
        if name not in limits:
            continue
        value, limit = numbers[name], limits[name]
        good = np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        rows.append((name, float(value), float(limit)))
    return ok, rows
