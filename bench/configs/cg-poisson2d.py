"""Plain reference of ``cg-poisson2d.json``: the 2-D 5-point Poisson
operator and unpreconditioned conjugate gradient.

Written from the configuration alone, without anything of the program
under test. The operator is applied as a stencil on the grid, not from
the ELL planes the program is given.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def poisson_matvec_f64(x: np.ndarray, side: int) -> np.ndarray:
    """A @ x in float64 on the host: 4 x minus the four grid neighbours,
    with zero outside the grid."""
    g = np.asarray(x, np.float64).reshape(side, side)
    y = 4.0 * g
    y[1:, :] -= g[:-1, :]
    y[:-1, :] -= g[1:, :]
    y[:, 1:] -= g[:, :-1]
    y[:, :-1] -= g[:, 1:]
    return y.reshape(-1)


def true_relative_residual(b, x, side: int) -> float:
    """||b - A x|| / ||b|| in float64 on the host."""
    b = np.asarray(b, np.float64)
    r = b - poisson_matvec_f64(x, side)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def _matvec(x, side: int):
    g = x.reshape(side, side)
    z_row = jnp.zeros((1, side), x.dtype)
    z_col = jnp.zeros((side, 1), x.dtype)
    up = jnp.concatenate([z_row, g[:-1, :]], axis=0)
    down = jnp.concatenate([g[1:, :], z_row], axis=0)
    left = jnp.concatenate([z_col, g[:, :-1]], axis=1)
    right = jnp.concatenate([g[:, 1:], z_col], axis=1)
    return (4 * g - up - down - left - right).reshape(-1)


@functools.partial(jax.jit, static_argnames=("side", "max_iters", "dtype"))
def cg(b, *, side: int, tol: float, max_iters: int, dtype=jnp.float32):
    """Textbook CG from x0 = 0 computed in ``dtype``, stopping when
    ||r|| < tol ||b|| or after ``max_iters`` iterations. Returns
    ``(x, rr)`` with ``x`` in float32 and ``rr`` = ||r||^2 of the
    recurrence."""
    b = b.astype(dtype)
    thresh = (tol * tol) * jnp.vdot(b, b).astype(jnp.float32)

    def cond(s):
        k, _, _, _, rr = s
        return (k < max_iters) & (rr.astype(jnp.float32) >= thresh)

    def body(s):
        k, x, r, p, rr = s
        ap = _matvec(p, side)
        alpha = rr / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.vdot(r, r)
        p = r + (rr_new / rr) * p
        return k + 1, x, r, p, rr_new

    s = (0, jnp.zeros_like(b), b, b, jnp.vdot(b, b))
    _, x, _, _, rr = jax.lax.while_loop(cond, body, s)
    return x.astype(jnp.float32), rr.astype(jnp.float32)
