"""Plain reference of ``jacobi2d5pt-4chip.json``: the 2-D 5-point Jacobi
sweep, and the same sweep run band by band over the devices of a field
too large for one of them.

Written from the configuration alone, in ``jax.numpy``, without anything
of the program under test. Each step replaces every interior cell by the
mean of itself and its four axis neighbours; the outermost cell of each
side stays frozen (Dirichlet).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

RADIUS = 1


def step(x):
    w = jnp.asarray(1.0 / 5.0, x.dtype)
    mid = (w * x[1:-1, 1:-1] + w * x[:-2, 1:-1] + w * x[2:, 1:-1]
           + w * x[1:-1, :-2] + w * x[1:-1, 2:])
    return x.at[1:-1, 1:-1].set(mid.astype(x.dtype))


@functools.partial(jax.jit, static_argnames=("steps", "dtype"))
def run(x, *, steps: int, dtype=jnp.float32):
    """``steps`` sweeps of ``x`` computed in ``dtype``, returned in
    ``x.dtype``. A band of rows cut from a larger field is treated as a
    field of its own: its first and last row stay frozen, so only rows at
    least ``steps * RADIUS`` from a cut edge are exact."""
    with jax.default_matmul_precision("highest"):
        y = jax.lax.fori_loop(0, steps, lambda _, s: step(s),
                              x.astype(dtype))
    return y.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("steps", "dtype", "keep"))
def _band(pieces, *, steps, dtype, keep):
    """``run`` over the rows ``pieces`` make together, rows ``keep`` of
    the result kept."""
    return run(jnp.concatenate(pieces), steps=steps,
               dtype=dtype)[keep[0]:keep[1]]


def run_banded(x, *, steps: int, bands: int, dtype=jnp.float32):
    """``run(x, steps=steps, dtype=dtype)`` in ``bands`` bands of rows.

    Band ``i`` is computed on the device that holds the first of its rows
    in ``x``, from its rows and ``steps * RADIUS`` rows of each
    neighbour, copied there; of the result it keeps its own rows, which
    are exact. The bands are dispatched together and run at once on their
    devices; no device holds more than one band. The result is laid out
    as ``x``: one array per band on its device where ``x`` is split into
    ``bands`` row shards, else one array on ``x``'s device."""
    H = x.shape[0]
    if H % bands:
        raise ValueError(f"{H} rows do not split into {bands} bands")
    h, halo = H // bands, steps * RADIUS
    shards = sorted(((s.index[0].start or 0, s.data)
                     for s in x.addressable_shards), key=lambda s: s[0])
    outs = []
    for i in range(bands):
        lo, hi = max(0, i * h - halo), min(H, (i + 1) * h + halo)
        device = next(iter([d for s0, d in shards if s0 <= i * h][-1]
                           .devices()))
        pieces = []
        for s0, data in shards:
            a, b = max(lo, s0), min(hi, s0 + data.shape[0])
            if a < b:
                piece = data if (a, b) == (s0, s0 + data.shape[0]) \
                    else data[a - s0:b - s0]
                pieces.append(jax.device_put(piece, device))
        keep = (i * h - lo, i * h - lo + h)
        outs.append(_band(tuple(pieces), steps=steps, dtype=dtype,
                          keep=keep))
    if len(shards) == bands:
        return jax.make_array_from_single_device_arrays(x.shape, x.sharding,
                                                        outs)
    return jnp.concatenate(outs)
