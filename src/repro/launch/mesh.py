"""Production meshes.

Functions (never module-level constants) so importing this module never
touches jax device state.  The single-pod production mesh is 16×16 = 256
chips (data × model); the multi-pod mesh adds a leading pod axis:
2 × 16 × 16 = 512 chips.  Pods are data-parallel replicas by default (the
"pod" axis joins "data" in every batch/optimizer sharding rule), which keeps
cross-pod traffic to gradient reduction — the right default for DCN-connected
pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.config import MeshConfig


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    if multi_pod:
        return MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))
    return MeshConfig(shape=(16, 16), axes=("data", "model"))


def make_mesh_from_config(cfg: MeshConfig):
    return _make_mesh(tuple(cfg.shape), tuple(cfg.axes))
