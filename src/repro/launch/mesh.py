"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 CPU device.

Production target: TPU v5e-class pods, 256 chips each.
  single-pod: (16, 16)    axes (data, model)
  multi-pod:  (2, 16, 16) axes (pod, data, model)   # 512 chips

The ``pod`` axis doubles as the DR-FL *client* axis in the federated
multi-pod mapping (see repro.core.aggregation.fl_allreduce).
"""
from __future__ import annotations

import jax


def _auto(axes):
    # jax.make_mesh defaults to Explicit axes, which with_sharding_constraint
    # in the launch steps rejects; these meshes are annotated by hand
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh for in-test dry-runs (requires >=8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))
