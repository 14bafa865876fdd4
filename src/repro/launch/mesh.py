"""Production mesh factory (assignment contract).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state — the F2 portability rule (the dry-run sets
``XLA_FLAGS`` before first jax init; tests see 1 device)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax


# the chip the production meshes are made of (roofline.hw.PEAKS key).
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Small simulated meshes for tests/examples (host devices)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


SERVING_AXES = {1: ("model",), 2: ("data", "model"),
                3: ("pod", "data", "model")}


def serving_mesh(shape: Tuple[int, ...], tp_axis: str = "model"):
    """Mesh for the sharded serving path (``cfg.mesh_shape``): the axis
    names are keyed by rank so the LAST axis is always the
    tensor-parallel one, matching ``DEFAULT_RULES`` ("heads"/"ff"/...
    -> "model").  Raises with an actionable message when the host does
    not expose enough devices (CPU CI simulates them via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which must
    be set before the first jax call of the process)."""
    shape = tuple(int(s) for s in shape)
    if not shape:
        raise ValueError("serving_mesh: empty mesh_shape")
    try:
        axes = SERVING_AXES[len(shape)]
    except KeyError:
        raise ValueError(
            f"serving_mesh: mesh_shape {shape} has rank {len(shape)}; "
            f"supported ranks are 1 (model,), 2 (data, model), "
            f"3 (pod, data, model)") from None
    if tp_axis != axes[-1]:
        raise ValueError(
            f"serving_mesh: tp_axis {tp_axis!r} must name the last mesh "
            f"axis {axes[-1]!r} for rank-{len(shape)} mesh_shape {shape}")
    need = 1
    for s in shape:
        need *= s
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"serving_mesh: mesh_shape {shape} needs {need} devices but "
            f"only {have} are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before the "
            f"first jax call of the process")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
