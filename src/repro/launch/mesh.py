"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state — required because the
dry-run re-initializes jax with 512 placeholder host devices.
"""

from __future__ import annotations

import jax

from repro.config import MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig
from repro.core.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_cfg_for(*, multi_pod: bool = False) -> MeshConfig:
    return MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH


def make_local_mesh():
    """Whatever devices exist locally (smoke tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
