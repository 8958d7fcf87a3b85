"""Production mesh construction + named-axis conventions.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; tests see the
real 1-CPU topology).

Axes:
  single pod : (16, 16)        -> ("data", "model")       = 256 chips
  multi-pod  : (2, 16, 16)     -> ("pod", "data", "model") = 512 chips

"pod" and "data" together form the FSDP/batch axes (params and optimizer
state sharded over both; batch split over both); "model" is the tensor-
parallel axis. DCN (inter-pod) traffic rides only the "pod" axis —
gradient all-reduce — which is the standard multi-pod training topology.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_abstract_mesh(
    shape: Sequence[int], axes: Sequence[str]
) -> jax.sharding.AbstractMesh:
    """Device-free mesh for sharding-rule evaluation."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def _mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axes: the sharding hints
    (``models.hints``) and the ``shard_map`` specs name mesh axes that
    GSPMD propagates, which ``Explicit`` axes (the make_mesh default)
    refuse in ``with_sharding_constraint``."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))


def make_host_serve_mesh(model_parallel: Optional[int] = None
                         ) -> jax.sharding.Mesh:
    """("data", "model") mesh over the local devices with a real TP axis.

    For multi-device CPU runs (XLA_FLAGS=--xla_force_host_platform_
    device_count=N) exercising the sharded ``pqs_dot`` serving path:
    puts as much of the device count on "model" as divides it (or the
    requested ``model_parallel``), the rest on "data".
    """
    n = len(jax.devices())
    tp = model_parallel or (n if n % 2 or n < 4 else n // 2)
    if n % tp:
        raise ValueError(f"model_parallel={tp} does not divide {n} devices")
    return _mesh((n // tp, tp), ("data", "model"))


def shrink_serve_mesh(
    mesh: jax.sharding.Mesh,
    lost: int,
    model_parallel: Optional[int] = None,
) -> jax.sharding.Mesh:
    """("data", "model") mesh over the survivors after losing ``lost`` devices.

    Drops the last ``lost`` devices of ``mesh`` (the simulated failed
    members) and rebuilds the serve-mesh layout over what remains —
    same TP heuristic as ``make_host_serve_mesh`` unless
    ``model_parallel`` pins it. Pass the result to
    ``ServingFleet.remesh_engine`` / ``ServingEngine.remesh``; the
    sharded integer projections are bit-exact at any mesh shape, so
    decode resumes with identical tokens on the smaller fleet.
    """
    devices = list(mesh.devices.flatten())
    if not 0 < lost < len(devices):
        raise ValueError(
            f"lost={lost} must leave at least 1 of {len(devices)} devices"
        )
    import numpy as np

    survivors = devices[: len(devices) - lost]
    n = len(survivors)
    tp = model_parallel or (n if n % 2 or n < 4 else n // 2)
    if n % tp:
        raise ValueError(f"model_parallel={tp} does not divide {n} survivors")
    grid = np.asarray(survivors).reshape(n // tp, tp)
    return jax.sharding.Mesh(grid, ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"


def axis_size(mesh: jax.sharding.Mesh, *names: str) -> int:
    out = 1
    for n in names:
        if n in mesh.axis_names:
            out *= mesh.shape[n]
    return out
