"""Shared kernel machinery: stencil specifications (Table III of the
paper) and the VMEM accounting every Pallas kernel requests its scoped
limit by.

A ``StencilSpec`` is a pure description — offsets + weights — consumed by
the Pallas kernels (``stencil2d.py``/``stencil3d.py``), the jnp oracles
(``ref.py``) and the system-level solvers (``solvers/stencil.py``).

Boundary semantics used everywhere in this repo: the outermost ``radius``
cells of the domain are Dirichlet (frozen); only the interior is updated.
This matches how the halo region is treated in the paper (never cached,
never updated by the owning kernel).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Sequence

import jax.numpy as jnp

#: VMEM a kernel requests beyond its own buffers and temporaries estimate,
#: for Mosaic's internal scratch and register spills. Each kernel passes
#: its estimate plus this as ``vmem_limit_bytes``: Mosaic's default scoped
#: limit is 16 MiB on a v5e, and the limit may go up to the chip's whole
#: VMEM (``core.hardware.Chip.onchip_bytes``), which the planner's budget
#: matches.
VMEM_HEADROOM_BYTES = 4 << 20


def vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer: its two minor axes padded to the memory
    tile (8 sublanes of 32-bit words, 128 lanes); a 1-D buffer to whole
    1024-element vregs."""
    itemsize = jnp.dtype(dtype).itemsize
    if len(shape) < 2:
        return -(-math.prod(shape) // 1024) * 1024 * itemsize
    sub = 8 * max(1, 4 // itemsize)
    *lead, s, lanes = shape
    return (math.prod(lead) * -(-s // sub) * sub * -(-lanes // 128) * 128
            * itemsize)


@dataclasses.dataclass(frozen=True)
class StencilSpec:
    name: str
    ndim: int
    offsets: tuple[tuple[int, ...], ...]
    weights: tuple[float, ...]

    def __post_init__(self):
        assert len(self.offsets) == len(self.weights)
        assert all(len(o) == self.ndim for o in self.offsets)

    @property
    def radius(self) -> int:
        return max(max(abs(c) for c in o) for o in self.offsets)

    @property
    def npoints(self) -> int:
        return len(self.offsets)

    @property
    def flops_per_cell(self) -> int:
        # one multiply + one add per point (paper Table III convention)
        return 2 * self.npoints

    # -- compute helpers (pure jnp; usable inside Pallas kernel bodies) -----

    def apply_rows(self, x, lo: int, hi: int):
        """Updated values of leading-axis rows [lo, hi) of ``x``.

        ``x`` must contain rows [lo - radius, hi + radius). Non-leading-axis
        borders are frozen (copied through from ``x``). ``lo``/``hi`` are
        static Python ints, so all slices are static.
        """
        r = self.radius
        acc = None
        for off, w in zip(self.offsets, self.weights):
            d0, rest = off[0], off[1:]
            idx = [slice(lo + d0, hi + d0 if hi + d0 != 0 else None)]
            for ax, d in enumerate(rest):
                n = x.shape[1 + ax]
                idx.append(slice(r + d, n - r + d))
            term = w * x[tuple(idx)]
            acc = term if acc is None else acc + term
        # Re-attach the frozen border strips by concatenation, innermost
        # axis first (Mosaic lowers no scatter, so no ``.at[].set``).
        out = acc.astype(x.dtype)
        for d in range(self.ndim - 1, 0, -1):
            def strip(s, d=d):
                idx = ([slice(lo, hi)]
                       + [slice(r, x.shape[k] - r) for k in range(1, d)]
                       + [s] + [slice(None)] * (self.ndim - 1 - d))
                return x[tuple(idx)]
            n = x.shape[d]
            out = jnp.concatenate(
                [strip(slice(0, r)), out, strip(slice(n - r, n))], axis=d)
        return out


def _star(ndim: int, radius: int) -> list[tuple[int, ...]]:
    offs = [tuple([0] * ndim)]
    for ax in range(ndim):
        for d in range(1, radius + 1):
            for s in (-d, d):
                o = [0] * ndim
                o[ax] = s
                offs.append(tuple(o))
    return offs


def _box(ndim: int, radius: int) -> list[tuple[int, ...]]:
    return list(itertools.product(range(-radius, radius + 1), repeat=ndim))


def _poisson3d() -> list[tuple[int, ...]]:
    """Classic 19-point 3D Poisson stencil: 3x3x3 cube minus the 8 corners."""
    return [o for o in _box(3, 1) if sum(abs(c) for c in o) <= 2]


def _3d17pt() -> list[tuple[int, ...]]:
    """A fixed symmetric 17-point stencil: r=1 star (7) + 4 xy-diagonals +
    r=2 axis points (6). Point count follows the paper's Table III; the
    exact geometry is not specified there, and any fixed 17-point stencil
    exercises the same per-cell traffic."""
    offs = _star(3, 1)
    offs += [(0, 1, 1), (0, 1, -1), (0, -1, 1), (0, -1, -1)]
    offs += [(2, 0, 0), (-2, 0, 0), (0, 2, 0), (0, -2, 0), (0, 0, 2), (0, 0, -2)]
    return offs


def _mk(name: str, ndim: int, offsets: Sequence[tuple[int, ...]]) -> StencilSpec:
    n = len(offsets)
    # Jacobi-style averaging weights: spectrally stable over thousands of
    # steps, so long-horizon tests don't overflow.
    w = tuple(1.0 / n for _ in offsets)
    return StencilSpec(name, ndim, tuple(offsets), w)


# Table III of the paper: benchmark(stencil order, flops/cell).
BENCHMARKS: dict[str, StencilSpec] = {
    "2d5pt": _mk("2d5pt", 2, _star(2, 1)),
    "2ds9pt": _mk("2ds9pt", 2, _star(2, 2)),
    "2d13pt": _mk("2d13pt", 2, _star(2, 3)),
    "2d17pt": _mk("2d17pt", 2, _star(2, 4)),
    "2d21pt": _mk("2d21pt", 2, _star(2, 5)),
    "2ds25pt": _mk("2ds25pt", 2, _star(2, 6)),
    "2d9pt": _mk("2d9pt", 2, _box(2, 1)),
    "2d25pt": _mk("2d25pt", 2, _box(2, 2)),
    "3d7pt": _mk("3d7pt", 3, _star(3, 1)),
    "3d13pt": _mk("3d13pt", 3, _star(3, 2)),
    "3d17pt": _mk("3d17pt", 3, _3d17pt()),
    "3d27pt": _mk("3d27pt", 3, _box(3, 1)),
    "poisson": _mk("poisson", 3, _poisson3d()),
}


def get_spec(name: str) -> StencilSpec:
    return BENCHMARKS[name]
