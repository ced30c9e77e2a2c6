"""Production meshes + logical-axis rules.

Single pod  : (16, 16)     axes ("data", "model")          = 256 chips
Multi-pod   : (2, 16, 16)  axes ("pod", "data", "model")   = 512 chips

`make_production_mesh` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — only the dry-run
process sets XLA_FLAGS for 512 host devices.
"""

from __future__ import annotations

import jax

from repro import sharding as shardlib


def _auto(n: int) -> tuple:
    # jax.make_mesh defaults to Explicit axes; sharding.constrain places
    # activations with with_sharding_constraint, which needs Auto axes
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_context(mesh=None, *, multi_pod: bool = False) -> shardlib.MeshContext:
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    return shardlib.MeshContext(
        mesh=mesh, rules=shardlib.default_rules(multi_pod="pod" in mesh.axis_names)
    )


def single_device_context() -> shardlib.MeshContext:
    """1-device mesh for CPU smoke runs of the launch drivers."""
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))
    return shardlib.MeshContext(mesh=mesh, rules=shardlib.default_rules(False))
