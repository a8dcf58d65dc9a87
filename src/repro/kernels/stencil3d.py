"""3D PERKS stencils (3d7pt/3d13pt/3d17pt/3d27pt/poisson).

The blocking machinery in ``stencil2d.py`` is generic over rank — it blocks
along the leading axis and ``StencilSpec.apply_rows`` handles the rest — so
3D reuses it directly with z-plane streaming. This mirrors the paper's 3D
implementation (§V-B): "2D planes are loaded one after the other", except
here the *resident planes* additionally survive across time steps in VMEM.

The only 3D-specific piece is the default streaming granularity: subtiles
are z-plane groups sized so a subtile (plus halo planes) fits comfortably in
VMEM next to the resident region.
"""
from __future__ import annotations



import math

import jax.numpy as jnp

from repro.core.hardware import Chip, TPU_V5E
from repro.kernels.common import StencilSpec
# rank-generic kernels, re-exported so they stay importable from the 3D module
from repro.kernels.stencil2d import (  # noqa: F401
    deep_vmem_bytes,
    perks_vmem_bytes,
    row_align,
    stencil_baseline_step,
    stencil_perks,
    stencil_perks_deep,
    stencil_resident,
)


__all__ = [
    "stencil_perks",
    "stencil_perks_deep",
    "stencil_resident",
    "stencil_baseline_step",
    "plan_resident_planes",
]

_DTYPES = {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32, 8: jnp.float64}


def plan_resident_planes(
    shape: tuple[int, ...],
    dtype_bytes: int,
    spec: StencilSpec,
    *,
    chip: Chip = TPU_V5E,
    sub_rows: int = 8,
    fuse_steps: int = 1,
    schedule: str = "shallow",
) -> int:
    """How many leading planes (rows in 2D) can stay VMEM-resident.

    The PERKS occupancy analysis (§IV-D) on TPU: the kernel's whole VMEM
    footprint — its streaming buffers and halo stashes, its compute
    temporaries and headroom (``perks_vmem_bytes``/``deep_vmem_bytes``,
    the same figure the kernel requests as its scoped-VMEM limit) — must
    fit ``chip.onchip_bytes``; every byte left holds resident planes.
    Returns a plane count in [0, shape[0]], a multiple of the leading
    axis's tile rows when partial.

    Temporal blocking widens the working set. ``schedule="shallow"``
    (``stencil_perks``): ``fuse_steps=t`` grows the block window and the
    compute temporaries with the ``radius*t`` halo (DESIGN.md §4).
    ``schedule="deep"`` (``stencil_perks_deep``): the wavefront scheme
    instead keeps (2t+5) block buffers alive
    (``core.cache_policy.deep_scratch_rows``, DESIGN.md §12) — the window
    no longer widens with t, so the working set grows with the *buffer
    count*, not the halo width. Either way, deeper fusion trades resident
    planes for fewer HBM passes.
    """
    if schedule not in ("shallow", "deep"):
        raise ValueError(
            f"schedule must be 'shallow' or 'deep', got {schedule!r}")
    dtype = _DTYPES[dtype_bytes]
    footprint = perks_vmem_bytes if schedule == "shallow" else deep_vmem_bytes
    row_bytes = math.prod(shape[1:]) * dtype_bytes
    one = footprint(shape, dtype, spec, cached_rows=1, sub_rows=sub_rows,
                    fuse_steps=fuse_steps)
    planes = int((chip.onchip_bytes - one) // row_bytes) + 1
    planes = max(0, min(planes, shape[0]))
    if 0 < planes < shape[0]:
        planes = planes // row_align(shape, dtype) * row_align(shape, dtype)
        min_planes = spec.radius * (fuse_steps if schedule == "shallow"
                                    else 1)
        if planes < min_planes:
            planes = 0
    return planes
