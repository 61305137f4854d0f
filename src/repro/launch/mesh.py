"""Production mesh construction.

Targets (task brief): TPU v5e, 8 chips/node.
  * single-pod — (16, 16)    = 256 chips, axes ("data", "model")
  * multi-pod  — (2, 16, 16) = 512 chips, axes ("pod", "data", "model")

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state — the dry-run sets
XLA_FLAGS before first jax init and only then calls it.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

CHIPS_PER_NODE = 8


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh with Auto axis types (tests / AFD role meshes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def nodes_in_mesh(mesh) -> int:
    return int(np.prod(list(mesh.shape.values()))) // CHIPS_PER_NODE
