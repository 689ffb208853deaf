"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax init.

Mesh semantics (DESIGN.md §3): `pod` = task/RSU federation instance,
`data` = vehicles' client shards (data parallel), `model` = tensor/expert
parallel within a client group.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``. JAX 0.9 defaults new
    meshes to ``Explicit`` axes, under which an eager op on a mesh-sharded
    array (the fleet engine's fresh-draw scatter, ``server.fresh_padded``)
    must run inside ``jax.set_mesh``; the round program places its own
    arrays with ``NamedSharding`` and ``with_sharding_constraint``, which
    is the ``Auto`` contract."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_fleet_mesh(num_shards: int = 0, *, axis_name: str = "fleet"):
    """1-D device mesh over the IoV fleet axis (DESIGN.md §3).

    The fused round engine shards every fleet-stacked array's vehicle-lane
    axis over `axis_name`; model params, merged deltas and per-task scalars
    replicate. `num_shards=0` uses every visible device. Distinct from the
    production (data, model) mesh above: federation clients are the data
    parallelism here, and there is no tensor parallelism inside one
    vehicle's reduced backbone.
    """
    n = num_shards or jax.local_device_count()
    if n > jax.local_device_count():
        raise ValueError(
            f"fleet mesh wants {n} devices but only "
            f"{jax.local_device_count()} are visible (CI forces host "
            "devices via XLA_FLAGS=--xla_force_host_platform_device_count)")
    return _auto_mesh((n,), (axis_name,))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that shard the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
