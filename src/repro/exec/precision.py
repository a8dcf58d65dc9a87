"""Mixed precision as a Plan dimension (DESIGN.md §10).

The Krylov methods in this repo are memory-bound: the operator apply
(SpMV) streams the matrix, so dropping it to fp32 halves the dominant
traffic term — but the *reductions* (dot products) are where fp32
rounding actually bites: the recurrences in CG/BiCGStab re-ground on
``||r||^2``-scale quantities whose accumulated error is O(n·eps).
``precision="mixed"`` keeps the apply in the problem's storage dtype and
hardens only the reductions:

* with fp64 enabled (``jax_enable_x64``): accumulate the dot in fp64 and
  round once back to the storage dtype;
* without it (the TPU's default): pairwise summation of the fp32
  products in which every addition keeps its exact rounding error
  (TwoSum) — the accumulation error drops from O(n·eps) to
  O(eps) + O(n·eps²), at ~4x the adds and zero extra memory traffic
  (the terms are already on-chip).

``solve_refined`` layers iterative refinement on top: solve in working
precision, recompute the true residual, re-solve for the correction —
the classic mixed-precision driver, expressed as repeated ``execute``
calls so every tier/batch path gets it for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: Plan.precision values (plan.py validates against this).
PRECISIONS = ("uniform", "mixed")


def _x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def _two_sum(a: jax.Array, b: jax.Array):
    """Knuth's TwoSum: ``s = fl(a + b)`` and its exact rounding error."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def compensated_sum(x: jax.Array) -> jax.Array:
    """Compensated pairwise sum of a 1-D array (storage dtype out).

    The array is padded with zeros to a power of two and halved level by
    level, adjacent pairs added through ``_two_sum``; each level's
    rounding errors are summed into a running compensation, which is
    added to the total once at the end. Only the errors (already
    O(eps) of the partials) are summed natively, so what that loses is
    O(eps²).
    """
    (n,) = x.shape
    v = jnp.pad(x, (0, (1 << max(n - 1, 0).bit_length()) - n))
    comp = jnp.zeros((), x.dtype)
    while v.shape[0] > 1:
        pairs = v.reshape(-1, 2)
        v, err = _two_sum(pairs[:, 0], pairs[:, 1])
        comp = comp + jnp.sum(err)
    return v[0] + comp


def compensated_vdot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``vdot`` with a hardened accumulation (fp64 when enabled, TwoSum
    otherwise). The elementwise products still round once in the storage
    dtype — full fp64 accuracy needs ``jax_enable_x64``; what this
    removes is the O(n·eps) *accumulation* error that dominates for the
    registry-sized vectors."""
    if _x64_enabled() and a.dtype != jnp.float64:
        return jnp.vdot(a.astype(jnp.float64),
                        b.astype(jnp.float64)).astype(a.dtype)
    return compensated_sum((a * b).ravel())


def dot_for(precision: str):
    """The reduction the Krylov step functions should use under
    ``precision`` ('uniform' -> jnp.vdot, 'mixed' -> compensated)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    return compensated_vdot if precision == "mixed" else jnp.vdot


def solve_refined(problem, plan, *, rounds: int = 2, mesh=None):
    """Iterative refinement over ``execute``: solve, recompute the true
    residual, re-solve for the correction — ``rounds`` inner solves total.

    The inner solver is whatever ``plan`` says (any tier, any solver kind
    with a ``with_payload`` hook); the correction problems reuse the
    problem's own payload swap, so the plan/runner caches stay warm.
    Returns ``(x, rr)`` with ``rr`` the true squared residual norm of the
    accumulated solution.
    """
    from repro.exec.executor import execute
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    matvec = _operator_matvec(problem)
    b = problem.payload()
    x = jnp.zeros_like(b)
    cur = problem
    r = b
    for _ in range(rounds):
        dx, _ = execute(cur, plan, mesh=mesh)
        x = x + dx
        r = b - matvec(x)
        cur = problem.with_payload(r)
    return x, jnp.vdot(r, r)


def _operator_matvec(problem):
    """The problem's operator apply (for the refinement residual)."""
    mv = getattr(problem, "matvec", None)
    if mv is not None:
        return mv
    data, cols = getattr(problem, "data", None), getattr(problem, "cols", None)
    if data is None:
        raise NotImplementedError(
            f"{type(problem).__name__} exposes neither matvec nor ELL "
            f"planes; solve_refined cannot form the true residual")
    from repro.kernels.ref import spmv_ell
    return functools.partial(spmv_ell, data, cols)
