"""Plain reference of ``jacobi2d5pt.json``: the 2-D 5-point Jacobi sweep.

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
    y = jax.lax.fori_loop(0, steps, lambda _, s: step(s), x.astype(dtype))
    return y.astype(x.dtype)
