"""FL server: global-model bookkeeping, aggregation dispatch, evaluation.

Aggregation arms:
* DR-FL      — layer-aligned masked averaging (paper Step 2); optionally
               staleness-aware (FedAsync-style per-exit-layer decay) for
               updates arriving late under the async round engine
* HeteroFL   — width-slice scatter averaging
* ScaleFL    — depth+width scatter averaging (structure-tolerant)

Model-specific structure (masks, aggregation groups, stack templates,
evaluation forward passes) is delegated to the pluggable
:class:`repro.models.family.ModelFamily`; every entry point takes an
optional ``family`` (name or instance) and defaults to the registered
default family, so existing flat callsites keep working unchanged.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import aggregation
from repro.core.aggregation import (DELTA_MAG_CAP, delta_valid,
                                    layerwise_aggregate, sanitize_delta,
                                    tree_path_align, tree_path_items)
from repro.models.family import resolve_family


# ---------------------------------------------------------------------------
# evaluation (paper: small validation set on the cloud server)
# ---------------------------------------------------------------------------


def evaluate(params, x_val: np.ndarray, y_val: np.ndarray,
             batch: int = 256, family=None) -> np.ndarray:
    """Per-exit accuracy on the server validation set."""
    eval_batch = resolve_family(family).eval_fn()
    accs, n = [], 0
    with telemetry.span("eval"):
        for i in range(0, len(x_val), batch):
            xb = jnp.asarray(x_val[i:i + batch])
            yb = jnp.asarray(y_val[i:i + batch])
            # jaxlint: allow(host-sync-in-hot-path) -- one pull per eval batch; evaluate returns host accuracies by contract
            accs.append(np.asarray(eval_batch(params, xb, yb)) * len(xb))
            n += len(xb)
    return np.sum(accs, axis=0) / max(n, 1)


# ---------------------------------------------------------------------------
# DR-FL layer-aligned aggregation (list-based parity reference)
# ---------------------------------------------------------------------------


def staleness_scale(staleness: float, decay: float = 0.5) -> float:
    """FedAsync-style polynomial staleness discount: (1 + s)^(-decay).

    ``s`` counts how many aggregations advanced the global model between a
    client's dispatch and the arrival of its delta; s = 0 (fresh) maps to
    exactly 1.0, so the sync path is bit-for-bit unaffected."""
    if staleness <= 0:
        return 1.0
    return float((1.0 + float(staleness)) ** (-float(decay)))


def aggregate_drfl(global_params, deltas: List, model_idxs: List[int],
                   weights: Sequence[float], server_lr: float = 1.0,
                   staleness: Optional[Sequence[float]] = None,
                   staleness_decay: float = 0.5, family=None,
                   validate: bool = True, mag_cap: float = DELTA_MAG_CAP,
                   with_stats: bool = False):
    """DR-FL layer-aligned aggregation, optionally staleness-aware.

    With ``staleness`` given (one entry per delta: aggregations elapsed
    since that client's dispatch), each stale delta is down-weighted by
    ``staleness_scale(s, staleness_decay)`` APPLIED PER EXIT-LAYER: the
    decay is materialized as an alpha-valued mask over exactly the
    stages/exits the client's submodel holds and multiplied into the delta,
    so a lone stale contributor moves a layer by alpha * update (absolute
    FedAsync damping), not by the full update renormalized.  ``staleness``
    of all zeros (or None) reproduces the synchronous path bit-for-bit.

    ``validate`` quarantines poisoned deltas (non-finite anywhere, or any
    element beyond ``mag_cap``): the offender's mask is zeroed so the
    exact-rescale denominator removes it, and its elements are zeroed so
    nan can't leak through the numerator.  All-valid input is bit-for-bit
    the unvalidated path (mask * 1.0, element-exact ``where``).
    ``with_stats`` additionally returns the [N] device-side validity —
    callers batch the host pull (one device_get at their barrier)."""
    fam = resolve_family(family)
    masks = [fam.update_mask(global_params, m) for m in model_idxs]
    valid = None
    if validate:
        valid = [delta_valid(d, mag_cap) for d in deltas]
        deltas = [sanitize_delta(d) for d in deltas]
        masks = [jax.tree.map(lambda mm: mm * v.astype(jnp.float32), mask)
                 for mask, v in zip(masks, valid)]
    if staleness is not None and any(s > 0 for s in staleness):
        scaled = []
        for d, m, s in zip(deltas, model_idxs, staleness):
            a = staleness_scale(s, staleness_decay)
            if a == 1.0:
                scaled.append(d)
                continue
            smask = fam.update_mask(global_params, m, scale=a)
            scaled.append(jax.tree.map(
                lambda u, sm: (u.astype(jnp.float32) * sm).astype(u.dtype),
                d, smask))
        deltas = scaled
    out = layerwise_aggregate(global_params, deltas, masks, weights,
                              server_lr=server_lr)
    if with_stats:
        return out, (jnp.stack(valid) if valid is not None else None)
    return out


# ---------------------------------------------------------------------------
# stacked DR-FL aggregation: [N, R, seg] rows -> Pallas layer_agg kernel
# ---------------------------------------------------------------------------
#
# A family's aggregation groups (``family.stack_groups`` — for layer-wise
# trees: stem + stages[i] + exits[i], the units ``family.update_mask``
# masks as wholes) each flatten into consecutive fixed-width segment rows
# (core.aggregation.StackTemplate); the per-client hold masks and staleness
# alphas become a [N, R] mask matrix, and the whole of DR-FL Step 2 is ONE
# fused kernel dispatch (interpret mode on CPU, the MXU kernel on TPU)
# instead of a tree.map over ~60 leaves per client.  The list-based path
# above stays as the parity reference.
#
# Each bucket's client rows are first written, one client at a time, into
# one [N, R, seg] stack of the round's clients, N pow2-padded
# (``_stack_bucket_rows``, a program per bucket shape), so the averaging
# program's shapes follow N alone and not which submodels the round's
# buckets hold, and a bucket's pad rows are never copied to full-model
# width.


@functools.partial(jax.jit,
                   static_argnames=("family", "model_idx", "validate",
                                    "mag_cap"),
                   donate_argnums=(0, 1, 2))
def _stack_bucket_rows(stack, mask, valid, global_params, delta, start, n,
                       *, family, model_idx, validate, mag_cap):
    """Write the first ``n`` client rows of a bucket's stacked submodel
    delta (leaves ``[P, ...]``) into rows ``start .. start + n - 1`` of
    ``stack`` [N, R, seg], ``mask`` [N, R] (the submodel's hold mask) and
    ``valid`` [N], in place; ``start`` and ``n`` are data, so the program
    is the bucket shape's alone.

    Quarantine (``validate``): a client row that is non-finite anywhere or
    exceeds ``mag_cap`` is written as zeros with a zero mask row — the
    denominator's exact rescale then removes it from the mean, and its
    zeros keep nan out of the numerator (0 * nan = nan) — and ``valid``
    records it.  All-valid input is bit-for-bit the unvalidated rows."""
    template = family.stack_template(global_params)
    held = family.held_groups(global_params, model_idx)
    row_mask = aggregation.group_row_mask(held, template)
    groups = family.stack_groups(delta)

    def one(i, carry):
        stack, mask, valid = carry
        row = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            groups)
        u = aggregation.stack_group_rows(row, template, held)   # [R, seg]
        m = row_mask
        ok = jnp.bool_(True)
        if validate:
            ok = aggregation.stacked_rows_valid(u[None], mag_cap)[0]
            u = jnp.where(ok, u, 0.0)
            m = m * ok.astype(m.dtype)
        r = start + i
        return (jax.lax.dynamic_update_index_in_dim(stack, u, r, 0),
                jax.lax.dynamic_update_index_in_dim(mask, m, r, 0),
                jax.lax.dynamic_update_index_in_dim(valid, ok, r, 0))

    return jax.lax.fori_loop(0, n, one, (stack, mask, valid))


@functools.partial(
    jax.jit,
    static_argnames=("family", "server_lr", "any_stale", "use_kernel",
                     "interpret"))
def _stacked_agg_program(global_params, stack, mask, weights, alphas, *,
                         family, server_lr, any_stale, use_kernel,
                         interpret):
    """DR-FL Step 2 over the round's stacked client rows as ONE jit
    program: masked-mean (Pallas kernel on TPU / fused einsum elsewhere),
    scatter the averaged rows back onto the global tree.  Compiled once
    per (family, number of rows N: the round's clients, pow2-padded).

    stack: [N, R, seg] client rows (zeros outside held groups and in
    quarantined rows, :func:`_stack_bucket_rows`); mask: [N, R] 0/1 hold
    masks; weights, alphas: [N]."""
    template = family.stack_template(global_params)
    rows = aggregation.stacked_masked_mean(
        stack, mask, weights, alphas if any_stale else None,
        interpret=interpret, use_kernel=use_kernel)
    new_groups = aggregation.unstack_apply(family.stack_groups(global_params),
                                           rows, template,
                                           server_lr=server_lr)
    return family.unstack_groups(global_params, new_groups)


def _client_rows(weights) -> int:
    """A bucket's client rows: through its last nonzero weight (the rows
    after it are pow2 padding)."""
    nz = np.flatnonzero(np.asarray(weights, np.float64))
    return int(nz[-1]) + 1 if len(nz) else 0


def aggregate_drfl_stacked(global_params, buckets, server_lr: float = 1.0,
                           staleness_decay: float = 0.5,
                           interpret: Optional[bool] = None,
                           use_kernel: Optional[bool] = None, family=None,
                           validate: bool = True,
                           mag_cap: float = DELTA_MAG_CAP,
                           with_stats: bool = False):
    """DR-FL layer-aligned aggregation over bucket-stacked deltas.

    ``buckets``: iterable of ``(model_idx, stacked_delta, weights,
    staleness)`` where ``stacked_delta`` is the submodel pytree with a
    leading participant axis ``[P, ...]`` (repro.fl.batch.BucketResult),
    ``weights`` has P data sizes, and ``staleness`` is None or P counts.
    A bucket's client rows run through its last nonzero weight; the rows
    after it are pow2 padding (weight 0.0, out of the weighted mean
    exactly) and are left out of the stack.  Staleness alphas are folded
    into the mask matrix numerator with the denominator kept at the 0/1
    hold mask (absolute FedAsync damping, same semantics as
    :func:`aggregate_drfl`); all-fresh input skips the rescale so it is
    exactly the plain masked mean.

    ``validate``/``mag_cap``: see :func:`_stack_bucket_rows` (quarantine
    of poisoned rows).  ``with_stats`` returns ``(params, valid)`` with
    the row validity left ON DEVICE — callers batch the pull — one entry
    per client row, in bucket order, then True for the stack's pad rows."""
    fam = resolve_family(family)
    with telemetry.span("aggregate"):
        parts, ws, alphas = [], [], []
        any_stale = False
        for model_idx, delta, weights, stal in buckets:
            n = _client_rows(weights)
            if n == 0:
                continue
            parts.append((int(model_idx), delta, n))
            ws += [float(x) for x in weights[:n]]
            if stal is None:
                alphas += [1.0] * n
            else:
                scales = [staleness_scale(s, staleness_decay)
                          for s in stal[:n]]
                any_stale = any_stale or any(a != 1.0 for a in scales)
                alphas += scales
        if not parts:
            return (global_params, None) if with_stats else global_params
        # the stack's rows are pow2-padded too (zeros of weight 0.0), so the
        # kernel reads the rows it read when each bucket came stacked whole
        n_all = 1 << (len(ws) - 1).bit_length()
        ws += [0.0] * (n_all - len(ws))
        alphas += [1.0] * (n_all - len(alphas))
        template = fam.stack_template(global_params)
        stack = jnp.zeros((n_all, template.n_rows, template.seg),
                          jnp.float32)
        mask = jnp.zeros((n_all, template.n_rows), jnp.float32)
        valid = jnp.ones((n_all,), bool)
        start = 0
        for model_idx, delta, n in parts:
            stack, mask, valid = _stack_bucket_rows(
                stack, mask, valid, global_params, delta, start, n,
                family=fam, model_idx=model_idx, validate=bool(validate),
                mag_cap=float(mag_cap))
            start += n
        out = _stacked_agg_program(
            global_params, stack, mask, jnp.asarray(ws, jnp.float32),
            jnp.asarray(alphas, jnp.float32), family=fam,
            server_lr=float(server_lr), any_stale=any_stale,
            use_kernel=use_kernel, interpret=interpret)
    if not validate:
        valid = None
    return (out, valid) if with_stats else out


def aggregate_drfl_from_list(global_params, deltas: List,
                             model_idxs: List[int],
                             weights: Sequence[float],
                             server_lr: float = 1.0,
                             staleness: Optional[Sequence[float]] = None,
                             staleness_decay: float = 0.5,
                             interpret: Optional[bool] = None,
                             use_kernel: Optional[bool] = None,
                             family=None, validate: bool = True,
                             mag_cap: float = DELTA_MAG_CAP,
                             with_stats: bool = False):
    """Stacked-kernel aggregation over FULL-STRUCTURE delta pytrees (the
    list-based :func:`aggregate_drfl` contract) — each delta becomes a
    P=1 bucket.  Used for parity testing the kernel path against the
    list-based reference on identical inputs."""
    fam = resolve_family(family)
    buckets = []
    for j, (d, m) in enumerate(zip(deltas, model_idxs)):
        sub = fam.submodel_tree(d, m)
        stal = None if staleness is None else [staleness[j]]
        buckets.append((m, jax.tree.map(lambda a: a[None], sub),
                        [weights[j]], stal))
    return aggregate_drfl_stacked(global_params, buckets,
                                  server_lr=server_lr,
                                  staleness_decay=staleness_decay,
                                  interpret=interpret,
                                  use_kernel=use_kernel, family=fam,
                                  validate=validate, mag_cap=mag_cap,
                                  with_stats=with_stats)


# ---------------------------------------------------------------------------
# HeteroFL / ScaleFL aggregation (width / depth+width scatter)
# ---------------------------------------------------------------------------


def _scatter_avg(gp, contribs):
    """contribs: list of (delta_leaf, weight); delta may be channel-sliced."""
    num = jnp.zeros(gp.shape, jnp.float32)
    den = jnp.zeros(gp.shape, jnp.float32)
    for u, w in contribs:
        pad = [(0, gs - us) for gs, us in zip(gp.shape, u.shape)]
        num = num + w * jnp.pad(u.astype(jnp.float32), pad)
        den = den + w * jnp.pad(jnp.ones(u.shape, jnp.float32), pad)
    avg = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)
    return (gp.astype(jnp.float32) + avg).astype(gp.dtype)


def aggregate_sliced(global_params, deltas: List, weights: Sequence[float],
                     validate: bool = True,
                     mag_cap: float = DELTA_MAG_CAP,
                     with_stats: bool = False):
    """Structure- and shape-tolerant scatter aggregation (HeteroFL/ScaleFL).

    Contributions are collected per TREE PATH: a client's (possibly
    depth-truncated, width-sliced) delta subtree is aligned against the
    global tree position-by-position, so aliased leaves — the same array
    object reachable at two paths, which an ``id()``-keyed table would
    silently merge — stay independent aggregation targets.

    ``validate`` quarantines poisoned deltas exactly as
    :func:`aggregate_drfl` does: the client's weight is scaled by its
    device-side validity (0 drops it from numerator AND denominator, and
    the shared total cancels, so surviving clients are renormalized
    exactly) and non-finite elements are zeroed."""
    valid = None
    if validate:
        valid = [delta_valid(d, mag_cap) for d in deltas]
        deltas = [sanitize_delta(d) for d in deltas]
    table: Dict[tuple, list] = {
        path: [] for path, _ in tree_path_items(global_params)}
    for j, (d, w) in enumerate(zip(deltas, weights)):
        wj = float(w) if valid is None else float(w) * valid[j].astype(
            jnp.float32)
        for path, leaf in tree_path_align(global_params, d):
            if leaf is not None:
                table[path].append((leaf, wj))
    wtot = float(sum(weights)) or 1.0

    def rebuild(gp, path=()):
        if isinstance(gp, dict):
            return {k: rebuild(v, path + (k,)) for k, v in gp.items()}
        if isinstance(gp, (list, tuple)):
            t = [rebuild(v, path + (i,)) for i, v in enumerate(gp)]
            return t if isinstance(gp, list) else tuple(t)
        contribs = table[path]
        if not contribs:
            return gp
        contribs = [(u, w / wtot) for u, w in contribs]
        return _scatter_avg(gp, contribs)

    out = rebuild(global_params)
    if with_stats:
        return out, (jnp.stack(valid) if valid is not None else None)
    return out
