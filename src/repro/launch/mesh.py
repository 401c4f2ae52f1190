"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices) -> Mesh:
    """``jax.make_mesh`` with Auto axis types on every axis."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single-pod 16x16 (data, model) or 2-pod 2x16x16 (pod, data, model).

    256 chips/pod (TPU v5e pod slice); the multi-pod mesh prepends a DCN
    ``pod`` axis that composes with ``data`` for cross-pod data parallelism.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices but only {len(devices)} are "
            f"visible; the dry-run entrypoint must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={ndev} before "
            f"importing jax")
    return _mesh(shape, axes, devices[:ndev])


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """General mesh helper used by tests and the elastic re-mesh planner."""
    devices = list(devices if devices is not None else jax.devices())
    ndev = int(np.prod(shape))
    return _mesh(tuple(shape), tuple(axes), devices[:ndev])


def single_device_mesh() -> Mesh:
    """1-device mesh with the production axis names (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


def simulated_mesh(ndev: int = 8,
                   axes: Sequence[str] = ("data",),
                   shape: Optional[Sequence[int]] = None) -> Mesh:
    """Data-parallel mesh over ``ndev`` host-simulated devices.

    The CPU-verifiable twin of :func:`make_production_mesh`: run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<ndev>`` (set before
    jax initializes — ``tests/conftest.py`` does this for the test suite)
    and every shard_map/psum path executes for real on one host. ``shape``
    defaults to ``(ndev,)`` for a single axis; multi-axis layouts (e.g.
    ``("pod", "data")``) must pass an explicit shape whose product is
    ``ndev``.
    """
    axes = tuple(axes)
    if shape is None:
        if len(axes) != 1:
            raise ValueError(
                f"simulated_mesh needs an explicit shape for axes {axes}")
        shape = (ndev,)
    shape = tuple(int(s) for s in shape)
    if int(np.prod(shape)) != ndev:
        raise ValueError(f"shape {shape} does not use {ndev} devices")
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"simulated mesh needs {ndev} devices but only {len(devices)} "
            f"are visible; set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={ndev} before importing jax")
    return _mesh(shape, axes, devices[:ndev])


@functools.lru_cache(maxsize=None)
def butterfly_mesh(mesh_shape: Tuple[int, ...]) -> Mesh:
    """Mesh for ``ButterflyConfig.mesh_shape``: ``(d,)`` -> ``("data",)``,
    ``(p, d)`` -> ``("pod", "data")``. Cached so trace-time callers
    (``models/common.linear_apply``) reuse one Mesh object per shape.

    Works over whatever devices are visible — real accelerators or
    simulated host devices alike — so the too-few-devices error spells out
    both recoveries."""
    mesh_shape = tuple(int(s) for s in mesh_shape)
    if len(mesh_shape) == 1:
        axes: Tuple[str, ...] = ("data",)
    elif len(mesh_shape) == 2:
        axes = ("pod", "data")
    else:
        raise ValueError(
            f"butterfly mesh_shape must be (data,) or (pod, data); got "
            f"{mesh_shape}")
    ndev = int(np.prod(mesh_shape))
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"butterfly mesh_shape {mesh_shape} needs {ndev} devices but "
            f"only {len(devices)} are visible; use a smaller mesh_shape on "
            f"this host, or — for a CPU simulation — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={ndev} before "
            f"importing jax (launch/train.py: --simulated-devices {ndev})")
    return _mesh(mesh_shape, axes, devices[:ndev])
