"""Mesh construction and axis queries.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state).  Target: TPU v5e, 16x16 = 256 chips per pod; the multi-pod
configuration stacks 2 pods (512 chips) behind a leading "pod" axis used for
data parallelism across the DCN/ICI boundary.

Axes the rest of the stack understands:

  * ``"pod"``   — optional leading data-parallel axis across pods;
  * ``"lanes"`` — optional cohort-lane axis: the fused/spmd engines stack
    clients sharing a split layer along a leading lane dimension, and a
    mesh with a ``lanes`` axis shards that dimension (each device holds
    only its lanes' client/server replicas, Adam moments, and batch
    slices) instead of replicating the whole cohort;
  * ``"data"``  — per-lane batch parallelism;
  * ``"model"`` — tensor parallelism (``launch/shardings.py`` recipes).

``MeshSpec`` is a device-free mesh description: ``axis_sizes`` /
``batch_axes`` / ``lane_axis`` accept either a live ``jax`` mesh or a
``MeshSpec``, so sharding recipes can be computed and validated (e.g. the
conformance tests over every registered arch) without faking devices.

Every helper builds over ``jax.devices()`` — which, after
``jax.distributed.initialize`` (``launch.distributed``), is the *global*
device list across all processes: the same ``make_lane_host_mesh(2)``
call yields a process-spanning mesh on a 2-host launch with no code
change (the spmd engine places process-local shards into it via
``jax.make_array_from_process_local_data``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

#: the cohort-lane mesh axis name (see launch/shardings.py recipes)
LANE_AXIS = "lanes"


@dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes without devices — enough to compute and validate
    PartitionSpec trees (``launch.shardings.train_state_specs``) off any
    topology, including ones larger than the running host."""

    axis_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]

    def __post_init__(self):
        if len(self.axis_shape) != len(self.axis_names):
            raise ValueError(f"MeshSpec shape {self.axis_shape} does not "
                             f"match axes {self.axis_names}")

    @property
    def shape(self) -> dict:
        return dict(zip(self.axis_names, self.axis_shape))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the engines place arrays
    with ``NamedSharding`` and let the SPMD partitioner resolve mixed
    shardings (an FSDP-sharded weight meeting a ``data``-sharded batch),
    which ``Explicit`` axes, the default since JAX 0.9, reject.  Every mesh
    in the repo is built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, lanes: int = 1):
    """The 256-chip (single-pod) / 512-chip (multi-pod) production mesh.
    ``lanes > 1`` factors a leading cohort-lane axis out of the 16-wide
    data axis (total chip count unchanged)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if lanes > 1:
        data = shape[-2]
        if data % lanes:
            raise ValueError(f"lanes={lanes} does not divide the data axis "
                             f"({data} chips); pick a divisor of {data}")
        shape = shape[:-2] + (lanes, data // lanes, shape[-1])
        axes = axes[:-2] + (LANE_AXIS, "data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over host CPU devices for tests/examples — e.g.
    ``make_host_mesh((2, 2, 1), ("lanes", "data", "model"))`` on a 4-device
    host splits cohort lanes over two devices and each lane's batch over
    the other two."""
    return make_mesh(shape, axes)


def make_lane_host_mesh(lanes: int, devices: Optional[int] = None):
    """The canonical ``(lanes, n/lanes, 1)`` lanes/data/model mesh over the
    host's devices (every visible one unless ``devices`` caps it): cohort
    lanes over the leading axis, each lane's batch over the rest."""
    n = devices if devices is not None else len(jax.devices())
    if lanes < 1 or n % lanes:
        raise ValueError(f"lanes={lanes} does not divide the {n} devices")
    return make_host_mesh((lanes, n // lanes, 1),
                          (LANE_AXIS, "data", "model"))


def axis_sizes(mesh) -> dict:
    """``{axis name: size}`` for a live mesh or a :class:`MeshSpec`."""
    return dict(mesh.shape)


def batch_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes a (per-lane) global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def lane_axis(mesh) -> Optional[str]:
    """The cohort-lane axis name if the mesh has one, else ``None``."""
    return LANE_AXIS if LANE_AXIS in mesh.axis_names else None
