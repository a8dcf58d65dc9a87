"""DIA SpMV: the gather-free matvec for operators whose nonzeros lie on
few diagonals.

Every structured-grid operator (2-D/3-D Poisson, HPCG's 27-point,
convection-diffusion, constant-band FEM) stores its nonzeros on a handful
of diagonals ``j - i = d``. For those, ``y = sum_d A_d * shift(x, d)`` is
the same SpMV on the same nonzeros as the ELL gather ``sum_k data[:, k] *
x[cols[:, k]]``, but it reads only contiguous, lane-dense slices of ``x``
and no index planes: the DIA format of the GPU sparse libraries (Bell &
Garland, SC'09). On a TPU the gather costs one scalar-address load per
element: on a v5e, CG on the 256^2 Poisson operator spent about 97% of
its device time in it, 2.4 ms an iteration, against under 10 µs for the
whole iteration with the DIA form, which is elementwise work that XLA
fuses (PERF.md sections 5 and 6).

``ell_to_dia`` runs once per operator on the host; ``spmv_dia`` is plain
jnp (no Pallas), correct under ``jax.vmap``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def ell_to_dia(data, cols) -> Optional[tuple[tuple[int, ...], np.ndarray]]:
    """The DIA form of the square ELL operator ``(data, cols)``, or None
    where the gather should stay.

    Each slot with nonzero data lies on diagonal ``cols[i, k] - i``; slots
    with zero data (ELL padding, which stores column 0) are ignored, so
    they create no diagonal. Returns the sorted offsets as Python ints and
    planes ``(D, n)`` in the value dtype, ``planes[j, i]`` the entry of
    row ``i`` on diagonal ``offsets[j]`` (zero where the row has none, so
    the matvec needs no mask; duplicate slots of one entry are summed, as
    the gather sums them).

    None where DIA would hold more bytes than the ELL's value and index
    planes together (``D * value bytes > K * (value + index bytes)``,
    ``D <= 2K`` for f32 values and int32 indices), so DIA never reads more
    than the gather does; also where the operator stores no nonzero, or a
    column outside ``[0, n)`` (which the gather would clamp or wrap).
    """
    data = np.asarray(data)
    cols = np.asarray(cols)
    n, k = data.shape
    rows, slots = np.nonzero(data)
    c = cols[rows, slots].astype(np.int64)
    if c.size == 0 or c.min() < 0 or c.max() >= n:
        return None
    diag = c - rows
    offsets = np.unique(diag)
    if offsets.size * data.itemsize > k * (data.itemsize + cols.itemsize):
        return None
    planes = np.zeros((offsets.size, n), data.dtype)
    np.add.at(planes, (np.searchsorted(offsets, diag), rows),
              data[rows, slots])
    return tuple(int(d) for d in offsets), planes


def spmv_dia(planes, offsets: tuple[int, ...], x: jax.Array) -> jax.Array:
    """y = A @ x for A in DIA form: ``y[i] = sum_j planes[j][i] *
    x[i + offsets[j]]``, with ``x`` zero-padded by the widest offset on
    each side and one static slice per diagonal. ``planes`` is a ``(D,
    n)`` array or a sequence of ``D`` rows."""
    n = x.shape[0]
    m = max(abs(d) for d in offsets)
    xp = jnp.pad(x, (m, m))
    y = None
    for j, d in enumerate(offsets):
        term = planes[j] * jax.lax.slice(xp, (m + d,), (m + d + n,))
        y = term if y is None else y + term
    return y
