"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod).
Multi-pod: 2 pods x 256 = 512 chips with a leading "pod" axis (DCN).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """A device mesh whose axes GSPMD shards automatically (jax's default
    is explicit sharding-in-types, which the sharding rules here predate)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist (tests / examples on CPU): (data=1, model=n)."""
    n = len(jax.devices())
    return _mesh((1, n), ("data", "model"))


def make_serve_mesh(spec: str):
    """Parse a ``--mesh DxM`` spec (e.g. ``1x4``) into a (data, model) mesh.

    ``D`` is the data axis (replicated serving replicas), ``M`` the model
    (tensor-parallel) axis the KV pools and weights shard over.  Needs
    ``D*M`` visible devices — on a single host, simulate with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes).
    """
    parts = spec.lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() for p in parts):
        raise ValueError(f"--mesh expects DxM (e.g. 1x4), got {spec!r}")
    d, m = int(parts[0]), int(parts[1])
    if d < 1 or m < 1:
        raise ValueError(f"--mesh axes must be >= 1, got {spec!r}")
    n = len(jax.devices())
    if d * m > n:
        raise ValueError(
            f"--mesh {spec} needs {d * m} devices but only {n} are visible; "
            f"simulate with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{d * m} (must be set before jax initializes)"
        )
    return _mesh((d, m), ("data", "model"))
