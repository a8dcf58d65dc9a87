"""Multi-chip substrate: mesh discovery, logical-axis sharding rules,
collectives, and pipeline parallelism.

The package maps the paper's device-wide barrier onto JAX collectives
(see docs/DESIGN.md §3): inside ``shard_map`` every per-step ``ppermute``
halo exchange / ``psum`` reduction is exactly the synchronisation point a
persistent kernel's ``grid.sync()`` provides on a single chip.

Modules:
  * ``mesh``        — device-mesh construction, discovery,
                      and elastic resharding helpers.
  * ``sharding``    — ``smap`` (shard_map wrapper), ``constrain`` and the
                      logical-axis -> mesh-axis rule engine.
  * ``collectives`` — halo exchange, reductions, sharded decode attention.
  * ``pipeline``    — GPipe-style pipeline parallelism over a mesh axis.
"""
from repro.dist import collectives, mesh, pipeline, sharding
from repro.dist.collectives import all_gather, halo_exchange, psum
from repro.dist.mesh import make_mesh, mesh_axis_size
from repro.dist.sharding import (Rules, active_rules, constrain, make_rules,
                                 smap, use_rules)

__all__ = [
    "collectives", "mesh", "pipeline", "sharding",
    "all_gather", "halo_exchange", "psum",
    "make_mesh", "mesh_axis_size",
    "Rules", "active_rules", "constrain", "make_rules", "smap", "use_rules",
]
