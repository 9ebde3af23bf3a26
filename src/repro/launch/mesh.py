"""Production mesh builders (functions, never module-level constants:
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.exchange.spec import ExchangeTopology


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types: the sharding mode this
    repo's GSPMD-style code (``with_sharding_constraint`` rules, MoE and
    pipeline bodies) is written for.  jax's own default is ``Explicit``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16, 16) over ("data", "model").
    Multi-pod: 2 pods = 512 chips (2, 16, 16) over ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_size(mesh) -> int:
    return mesh.shape["model"]


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes_of(mesh):
        n *= mesh.shape[a]
    return n


def exchange_topology_of(
    mesh,
    *,
    axis: str = "data",
    lanes_per_host: int | None = None,
    class_weights: tuple[float, ...] | None = None,
) -> ExchangeTopology:
    """Derive the exchange plane's :class:`ExchangeTopology` from a mesh.

    Lanes are the shards along ``axis``; ``lanes_per_host`` is how many of
    them share one physical host, read off the mesh's device placement
    (``process_index`` along the first row of ``axis``).  Mesh device order
    is process-major on multi-host deployments, matching the topology's
    host-major lane convention (lane ``j`` on host ``j // lanes_per_host``).

    Single-process meshes (CPU tests, ``xla_force_host_platform_device_count``
    simulations) have no process boundary to read — pass ``lanes_per_host``
    explicitly to model one (the two-host bench profile does), otherwise all
    lanes land on one host and every backend degenerates to its flat
    behavior.
    """
    num_lanes = mesh.shape[axis]
    if lanes_per_host is None:
        dims = list(mesh.axis_names)
        devs = mesh.devices.transpose(
            [dims.index(axis)] + [i for i, a in enumerate(dims) if a != axis]
        )
        procs = [d.process_index for d in devs.reshape(num_lanes, -1)[:, 0]]
        # contiguous run length of the first host along the axis; a
        # single-process mesh yields one host (= the flat world)
        lanes_per_host = next(
            (i for i, p in enumerate(procs) if p != procs[0]), num_lanes
        )
        lanes_per_host = max(lanes_per_host, 1)
    kw = {} if class_weights is None else {"class_weights": tuple(class_weights)}
    return ExchangeTopology(
        num_lanes=num_lanes, lanes_per_host=int(lanes_per_host), **kw
    )
