"""Data-parallel FleetState sharding — the million-device fleet mesh.

:class:`repro.core.fleet.FleetState` is a registered pytree of ``[n]``
arrays, and every Eq. 3–7 kernel (cost matrices, affordability masks,
charge, Top-K cut, factored summary) is elementwise or a small reduction
over that axis.  This module places those arrays across devices/hosts with
``jax.sharding`` — a 1-D :class:`Mesh` over a ``"fleet"`` axis and
:class:`NamedSharding` per field — so the jitted kernels run SPMD
data-parallel: each device owns ``n / mesh_size`` fleet rows, per-device
work never materialises the whole fleet, and the only cross-device traffic
per selection+energy step is the ``summary_width``-sized all-reduce inside
:func:`repro.core.fleet.fleet_summary` plus the tiny Top-K merge.

The rule machinery mirrors :mod:`repro.sharding.rules` (name-based logical
axes + divisibility fallback to replication): FleetState fields map to the
``("fleet",)`` logical axis through :data:`FLEET_RULES`, and any field
whose leading dim does not divide the mesh falls back to ``P()``
(replicated) instead of erroring — the same policy that lets one rule
table cover every model in ``rules.py``.

Public surface (one-line contracts):

* :data:`FLEET_AXIS` — the mesh-axis name (``"fleet"``).
* :func:`fleet_mesh` — 1-D Mesh over the local devices (or a prefix;
  more shards than devices is an error).
* :func:`fleet_spec_for` — PartitionSpec for one field (rule lookup +
  divisibility fallback).
* :func:`fleet_shardings` — FleetState-shaped pytree of NamedShardings.
* :func:`shard_fleet` — device_put the fleet onto the mesh.
* :func:`shard_agent_array` — row-shard one companion ``[n, ...]`` array
  (GRU hidden states, obs matrices) with the same fallback policy.
* :func:`unshard_fleet` — gather back to single-device host arrays.
* :func:`maybe_shard_fleet` — config-level entry: no-op below 2 shards.
* :func:`is_sharded` — True when a fleet's arrays live on a >1 mesh.

CPU note: a multi-device mesh on one host needs
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set BEFORE jax
initialises (the shard-smoke CI job and ``benchmarks/fleet_shard_bench.py``
do this); under the default single-device CPU runtime everything here
degrades to a no-op placement.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fleet import FleetState
from repro.sharding.rules import _mesh_size

#: mesh axis carrying the fleet's device axis
FLEET_AXIS = "fleet"

# field-name regex -> logical axes of the [n] array (rules.py-style table;
# every FleetState array field is 1-D over the fleet axis today, but the
# table keeps the mapping declarative and extensible, e.g. per-device
# feature matrices would add (r"features$", ("fleet", None))).
FLEET_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    (r".*", (FLEET_AXIS,)),
)

LOGICAL_TO_MESH = {FLEET_AXIS: (FLEET_AXIS,)}


def fleet_mesh(n_shards: Optional[int] = None, devices=None) -> Mesh:
    """1-D ``("fleet",)`` mesh over ``devices`` (default: all local jax
    devices), truncated to ``n_shards`` when given.  ``None``, ``0`` and
    ``-1`` all mean "all local devices" (matching the config convention
    ``fleet_mesh=-1``)."""
    devs = list(devices if devices is not None else jax.devices())
    if n_shards is not None and int(n_shards) >= 1:
        if int(n_shards) > len(devs):
            raise ValueError(f"fleet mesh of {int(n_shards)} shards asked "
                             f"for, but only {len(devs)} devices exist")
        devs = devs[:int(n_shards)]
    return Mesh(np.array(devs), (FLEET_AXIS,))


def fleet_spec_for(name: str, shape, mesh: Mesh) -> P:
    """PartitionSpec for one FleetState field: first matching rule in
    :data:`FLEET_RULES`, with silent fallback to replication when the
    fleet dim does not divide the mesh (same policy as
    :func:`repro.sharding.rules.spec_for`)."""
    if len(shape) == 0:
        return P()
    for pat, logical in FLEET_RULES:
        if re.search(pat, name):
            out = []
            for dim, ax in zip(shape, logical):
                mesh_axes = LOGICAL_TO_MESH.get(ax, ())
                if (mesh_axes and dim % _mesh_size(mesh, mesh_axes) == 0
                        and dim >= _mesh_size(mesh, mesh_axes)):
                    out.append(mesh_axes[0] if len(mesh_axes) == 1
                               else tuple(mesh_axes))
                else:
                    out.append(None)
            return P(*out)
    return P()


def shard_agent_array(x, mesh: Mesh, axis: int = 0):
    """Place one per-agent array (``[n, ...]``) on the mesh, row-sharded
    over :data:`FLEET_AXIS` along ``axis`` — the companion to
    :func:`shard_fleet` for arrays that ride WITH the fleet but live
    outside :class:`FleetState` (QMIX GRU hidden states ``[n, hidden]``,
    observation matrices ``[n, OBS_DIM]``).  Same divisibility policy as
    :func:`fleet_spec_for`: an agent dim that does not divide the mesh
    falls back to replication instead of erroring."""
    shape = np.shape(x)
    size = _mesh_size(mesh, (FLEET_AXIS,))
    if (len(shape) > axis and shape[axis] % size == 0
            and shape[axis] >= size):
        spec = [None] * len(shape)
        spec[axis] = FLEET_AXIS
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return jax.device_put(x, NamedSharding(mesh, P()))


def fleet_shardings(fleet: FleetState, mesh: Mesh) -> dict:
    """``{field: NamedSharding}`` placements for every FleetState array
    field (rule lookup + divisibility fallback per field)."""
    from repro.core.fleet import _ARRAY_FIELDS
    return {f: NamedSharding(
                mesh, fleet_spec_for(f, np.shape(getattr(fleet, f)), mesh))
            for f in _ARRAY_FIELDS}


def shard_fleet(fleet: FleetState, mesh: Mesh) -> FleetState:
    """Place every fleet array on the mesh (row-sharded over
    :data:`FLEET_AXIS`, replicated where indivisible).  numpy-backend
    fleets are promoted to jax arrays by the placement."""
    placements = fleet_shardings(fleet, mesh)
    return fleet.replace(**{f: jax.device_put(getattr(fleet, f), s)
                            for f, s in placements.items()})


def unshard_fleet(fleet: FleetState) -> FleetState:
    """Gather a (possibly sharded) fleet back to host numpy arrays — the
    DeviceState-compatibility / debugging path, NOT the hot loop."""
    from repro.core.fleet import _ARRAY_FIELDS
    return FleetState(
        **{f: np.asarray(getattr(fleet, f)) for f in _ARRAY_FIELDS},
        tiers=fleet.tiers, modes=fleet.modes)


def is_sharded(fleet: FleetState) -> bool:
    """True when the fleet's arrays are placed on a multi-device mesh."""
    r = fleet.remaining
    return (isinstance(r, jax.Array)
            and len(getattr(r.sharding, "device_set", ())) > 1)


def maybe_shard_fleet(fleet: FleetState, n_shards: int = 0) -> FleetState:
    """Config-level entry point (``FLConfig.fleet_mesh``): shard over
    ``n_shards`` devices when that is >= 2, otherwise return the fleet
    unchanged.  ``n_shards <= 1`` (the config default 0) keeps the legacy
    single-placement fleet — sharding is always opt-in; ``-1`` means "all
    local devices" (unsharded on a one-device runtime).  An explicit
    request for more shards than devices raises instead of quietly
    running unsharded."""
    want = len(jax.devices()) if n_shards == -1 else int(n_shards)
    if want < 2:
        return fleet
    return shard_fleet(fleet, fleet_mesh(want))
