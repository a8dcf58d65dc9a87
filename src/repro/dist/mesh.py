"""Device-mesh construction and elastic resharding helpers.

Everything here is a FUNCTION (no module-level jax device access) so that
importing ``repro.dist`` never locks the backend device count — the
dry-run and the subprocess-spawned multi-device tests both set
``XLA_FLAGS`` before the first mesh is built.

All meshes in this repo are Auto-typed: shard_map supplies explicit specs
everywhere.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with Auto axis types. Uses the first
    ``prod(axis_shapes)`` local devices when ``devices`` is not given."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
        devices=devices)


def mesh_axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    """Size of ``name`` in ``mesh``; 1 when the axis does not exist (so
    callers can branch on "is this axis actually parallel")."""
    return int(dict(mesh.shape).get(name, 1))


def discover_mesh(*, model_axis: Optional[int] = None,
                  axis_names: tuple[str, str] = ("data", "model")):
    """1D/2D mesh over whatever devices exist.

    ``model_axis=None`` picks the largest power-of-two divisor of the
    device count up to 8 (a TP degree that always divides head counts in
    the model zoo); ``model_axis=1`` degenerates to pure DP.
    """
    n = len(jax.devices())
    if model_axis is None:
        model_axis = 1
        while model_axis < 8 and n % (model_axis * 2) == 0:
            model_axis *= 2
    if n % model_axis:
        raise ValueError(f"{n} devices not divisible by model={model_axis}")
    return make_mesh((n // model_axis, model_axis), axis_names)


# -- elastic resharding ---------------------------------------------------------

def reshard(tree, shardings):
    """Move a pytree of (host or device) arrays onto new shardings.

    This is the elastic-restart primitive: a logical checkpoint written on
    one mesh lands on a different mesh/device count by round-tripping
    through the host view (``ckpt.restore`` passes target shardings here
    implicitly via ``device_put``).
    """
    return jax.tree.map(jax.device_put, tree, shardings)


def like_shardings(mesh: jax.sharding.Mesh, spec_tree):
    """NamedSharding tree matching a PartitionSpec tree on ``mesh``."""
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
