"""Aggregation operators: FedAvg (Eq. 2) and DR-FL layer-aligned averaging.

Paper Step 2: "layer-align averaging — the same parts of the network will be
aggregated".  A layer of the global model is updated with the data-size-
weighted mean of exactly those client gradients whose submodel contains the
layer; layers no client trained keep the previous global value.

Three deployment forms:
* :func:`layerwise_aggregate` — host/driver-side over a list of client
  updates (the original simulation path, kept as the parity reference).
* the STACKED form — client updates flattened into equal-width segment rows
  ``[N, R, seg]`` with a per-row mask matrix ``[N, R]``
  (:class:`StackTemplate` + :func:`stacked_masked_mean`), dispatched to the
  Pallas ``layer_agg`` kernel as ONE fused pass (interpret mode on CPU).
* :func:`fl_allreduce` — the same op expressed as a masked ``psum`` over the
  ``pod`` mesh axis (multi-pod production mapping; each pod is a client).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def tree_path_items(tree, _path=()):
    """Yield ``(path, leaf)`` for every leaf of a dict/list/tuple pytree.

    Paths are tuples of dict keys / sequence indices: positional identity,
    not object identity, so aliased leaves (the same array object reachable
    at two paths) keep distinct entries — the property the scatter
    aggregation table relies on."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from tree_path_items(v, _path + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from tree_path_items(v, _path + (i,))
    else:
        yield _path, tree


def tree_path_align(ref, other, _path=()):
    """Yield ``(path, other_leaf_or_None)`` for every leaf position of
    ``ref`` — ``None`` where ``other`` (a possibly depth-truncated /
    structure-poorer tree, e.g. a ScaleFL client delta) has no entry."""
    if isinstance(ref, dict):
        for k, v in ref.items():
            o = other[k] if (other is not None and k in other) else None
            yield from tree_path_align(v, o, _path + (k,))
    elif isinstance(ref, (list, tuple)):
        for i, v in enumerate(ref):
            o = (other[i] if (other is not None and i < len(other))
                 else None)
            yield from tree_path_align(v, o, _path + (i,))
    else:
        yield _path, other


#: default per-element magnitude ceiling for client deltas — far above any
#: legitimate local-SGD delta, so only corrupted/diverged payloads trip it
DELTA_MAG_CAP = 1e8


def delta_valid(delta, mag_cap: float = DELTA_MAG_CAP):
    """Device-side scalar bool: every leaf of ``delta`` is finite and within
    ``mag_cap`` in magnitude.  The per-client gate of the quarantine layer
    (graceful degradation: a poisoned update must never reach the global
    params)."""
    ok = jnp.asarray(True)
    for leaf in jax.tree.leaves(delta):
        fin = jnp.isfinite(leaf)
        ok = ok & fin.all()
        safe = jnp.where(fin, leaf, 0)
        ok = ok & (jnp.max(jnp.abs(safe), initial=0.0) <= mag_cap)
    return ok


def sanitize_delta(delta):
    """Zero every non-finite element.  Quarantine zeroes a bad client's
    MASK, but 0 * nan = nan, so the numerator needs finite operands; for
    all-finite deltas ``where`` is an exact element copy (bit-for-bit)."""
    return jax.tree.map(
        lambda u: jnp.where(jnp.isfinite(u), u, jnp.zeros_like(u)), delta)


def stacked_rows_valid(U, mag_cap: float = DELTA_MAG_CAP):
    """[N] bool from stacked client rows [N, R, seg]: finite everywhere and
    within ``mag_cap`` — vectorized :func:`delta_valid` for the stacked
    aggregation path."""
    fin = jnp.isfinite(U)
    safe = jnp.where(fin, U, 0.0)
    return (fin.all(axis=(1, 2))
            & (jnp.max(jnp.abs(safe), axis=(1, 2)) <= mag_cap))


def fedavg(updates: Sequence, weights: Optional[Sequence[float]] = None):
    """Plain FedAvg over pytrees (Eq. 2). ``weights`` ~ client data sizes."""
    n = len(updates)
    if weights is None:
        w = [1.0 / n] * n
    else:
        tot = float(sum(weights))
        w = [float(x) / tot for x in weights]
    return jax.tree.map(
        lambda *xs: sum(wi * x.astype(jnp.float32) for wi, x in zip(w, xs)
                        ).astype(xs[0].dtype),
        *updates)


def layerwise_aggregate(global_params, client_updates: List, client_masks: List,
                        weights: Optional[Sequence[float]] = None,
                        server_lr: float = 1.0):
    """DR-FL layer-aligned aggregation.

    global_params : pytree W_t
    client_updates: list of pytrees (client gradient/delta, SAME structure —
                    clients zero-fill layers they did not train)
    client_masks  : list of pytrees of 0/1 masks (from
                    :func:`repro.core.layerwise.stacked_update_mask`),
                    broadcastable leaf-wise against the updates
    weights       : client data sizes L_n (paper Eq. 2)

    Returns W_{t+1} = W_t + server_lr * masked weighted mean of updates.
    """
    n = len(client_updates)
    if weights is None:
        weights = [1.0] * n
    w = [float(x) for x in weights]

    def agg(gp, *leaves):
        ups = leaves[:n]
        msks = leaves[n:]
        num = sum(wi * m.astype(jnp.float32) * u.astype(jnp.float32)
                  for wi, u, m in zip(w, ups, msks))
        den = sum(wi * m.astype(jnp.float32) for wi, m in zip(w, msks))
        den = jnp.broadcast_to(den, num.shape) if hasattr(den, "shape") else den
        avg = jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0)
        return (gp.astype(jnp.float32) + server_lr * avg).astype(gp.dtype)

    return jax.tree.map(agg, global_params, *client_updates, *client_masks)


# ---------------------------------------------------------------------------
# stacked [N, R, seg] representation (feeds the Pallas layer_agg kernel)
# ---------------------------------------------------------------------------
#
# The kernel wants a uniform [N, L, D]; the CNN's layer groups span ~3 orders
# of magnitude in size, so a naive stack to [N, n_groups, max_group] wastes
# ~7x memory on padding.  Instead each group is padded to a multiple of a
# fixed segment width ``seg`` and laid out as consecutive ROWS of one
# [N, R, seg] array: the mask value is constant within a group, so every row
# of a group carries its group's mask entry and the kernel's per-layer
# masked mean is exact.  Padding waste is < n_groups * seg elements total.


class StackTemplate(NamedTuple):
    """Row layout of one model's parameters, grouped by aggregation unit."""
    seg: int                               # segment (row) width
    n_rows: int                            # R: total rows
    group_sizes: Tuple[int, ...]           # flat element count per group
    group_rows: Tuple[Tuple[int, int], ...]  # (row_start, row_stop) per group


#: row-count multiple of every stack template (the f32 sublane tile)
ROW_ALIGN = 8


def build_stack_template(group_trees: Sequence, seg: int = 1024
                         ) -> StackTemplate:
    sizes, rows, r = [], [], 0
    for tree in group_trees:
        n = int(sum(l.size for l in jax.tree.leaves(tree)))
        nr = max(1, -(-n // seg))
        sizes.append(n)
        rows.append((r, r + nr))
        r += nr
    # trailing zero rows (mask 0) align R to the kernel's 8-row f32 tile,
    # so layer_agg never has to copy the [N, R, seg] stack to pad it
    r = -(-r // ROW_ALIGN) * ROW_ALIGN
    return StackTemplate(seg=int(seg), n_rows=r, group_sizes=tuple(sizes),
                         group_rows=tuple(rows))


def _flat_group(tree, lead_axes: int = 0):
    """Concat a group's leaves into one flat vector (or [P, flat])."""
    leaves = jax.tree.leaves(tree)
    if lead_axes:
        return jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves],
            axis=1)
    return jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32) for l in leaves])


def stack_group_rows(group_trees: Sequence, template: StackTemplate,
                     held, stacked: bool = False):
    """Flatten held groups into segment rows.

    group_trees: one entry per HELD group, in global group order (entries
                 for unheld groups are skipped via ``held``);
    held:        boolean per global group;
    stacked:     leaves carry a leading participant axis [P, ...].

    Returns [R, seg] (or [P, R, seg]) float32 with zeros outside held groups.
    """
    it = iter(group_trees)
    parts = []
    lead = None
    for g, is_held in enumerate(held):
        r0, r1 = template.group_rows[g]
        nr, size = r1 - r0, template.group_sizes[g]
        if not is_held:
            parts.append(("zeros", nr))
            continue
        flat = _flat_group(next(it), lead_axes=1 if stacked else 0)
        pad = nr * template.seg - size
        if stacked:
            lead = flat.shape[0]
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
            parts.append(("rows", flat.reshape(lead, nr, template.seg)))
        else:
            flat = jnp.pad(flat, (0, pad))
            parts.append(("rows", flat.reshape(nr, template.seg)))
    tail = template.n_rows - (template.group_rows[-1][1]
                              if template.group_rows else 0)
    if tail:
        parts.append(("zeros", tail))
    out = []
    for kind, v in parts:
        if kind == "rows":
            out.append(v)
        elif stacked:
            out.append(jnp.zeros((lead, v, template.seg), jnp.float32))
        else:
            out.append(jnp.zeros((v, template.seg), jnp.float32))
    return jnp.concatenate(out, axis=1 if stacked else 0)


def group_row_mask(held, template: StackTemplate) -> jnp.ndarray:
    """Expand a per-group 0/1 vector to the per-row mask [R]."""
    m = jnp.zeros((template.n_rows,), jnp.float32)
    for g, is_held in enumerate(held):
        if is_held:
            r0, r1 = template.group_rows[g]
            m = m.at[r0:r1].set(1.0)
    return m


def stacked_masked_mean(U, mask01, weights, alphas=None, *, interpret=None,
                        use_kernel: Optional[bool] = None):
    """Masked weighted mean over clients on the stacked representation.

    U: [N, R, seg]; mask01: [N, R] 0/1 hold masks; weights: [N];
    alphas: optional [N] per-client staleness scales applied to the
    NUMERATOR only (FedAsync absolute damping) — the denominator keeps the
    0/1 hold mask, recovered from the kernel's single-mask contract by
    rescaling each row with (sum w*alpha*m) / (sum w*m).  ``alphas=None``
    skips the rescale entirely, so the fresh path is bit-for-bit the plain
    kernel output.  Returns [R, seg] float32.

    Dispatch: the Pallas ``layer_agg`` kernel on TPU (one fused VMEM pass
    per block), and the identical-math fused XLA einsum elsewhere —
    interpret-mode Pallas walks the R-row grid in a simulated loop, which
    is a testing tool, not a CPU execution path.  ``use_kernel=True``
    forces the kernel (tests pair it with ``interpret=True``).
    """
    from repro.kernels.layer_agg import layer_agg_op

    w = jnp.asarray(weights, jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    op = (lambda u, m, ww: layer_agg_op(u, m, ww, interpret=interpret)) \
        if use_kernel else _stacked_mean_ref
    if alphas is None:
        return op(U, mask01, w)
    a = jnp.asarray(alphas, jnp.float32)
    m_alpha = mask01 * a[:, None]
    out = op(U, m_alpha, w)
    den01 = (w[:, None] * mask01).sum(axis=0)
    den_a = (w[:, None] * m_alpha).sum(axis=0)
    ratio = jnp.where(den01 > 0, den_a / jnp.maximum(den01, 1e-12), 0.0)
    return out * ratio[:, None]


@jax.jit
def _stacked_mean_ref(U, mask, w):
    from repro.kernels.layer_agg import layer_agg_ref
    return layer_agg_ref(U, mask, w)


def unstack_apply(global_group_trees: Sequence, rows, template: StackTemplate,
                  server_lr: float = 1.0):
    """Apply averaged delta rows [R, seg] back onto the global group trees.

    Returns the list of updated group trees (same structures/dtypes);
    mirrors :func:`layerwise_aggregate`'s ``gp + server_lr * avg`` leaf op.
    """
    out = []
    for g, tree in enumerate(global_group_trees):
        r0, r1 = template.group_rows[g]
        flat = rows[r0:r1].reshape(-1)[:template.group_sizes[g]]
        leaves, treedef = jax.tree.flatten(tree)
        new_leaves, off = [], 0
        for l in leaves:
            d = flat[off:off + l.size].reshape(l.shape)
            new_leaves.append(
                (l.astype(jnp.float32) + server_lr * d).astype(l.dtype))
            off += l.size
        out.append(jax.tree.unflatten(treedef, new_leaves))
    return out


def fl_allreduce(update, mask, weight, axis_name: str = "pod"):
    """Masked layer-aligned aggregation as a collective (inside shard_map).

    Each pod contributes ``update`` (zero outside its submodel), ``mask``
    (its update mask) and scalar ``weight`` (data size).  Returns the
    aggregated delta every pod applies to its replica of the global model —
    DR-FL Step 2 as a single psum pair over the pod axis.
    """
    def one(u, m):
        num = jax.lax.psum(weight * m * u.astype(jnp.float32), axis_name)
        den = jax.lax.psum(weight * m, axis_name)
        return jnp.where(den > 0, num / jnp.maximum(den, 1e-12), 0.0).astype(u.dtype)

    return jax.tree.map(one, update, mask)
