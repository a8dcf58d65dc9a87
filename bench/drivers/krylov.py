"""Krylov cells: back-to-back solves with ``execute``, one right-hand side
each.

Set-up puts the operator on the device in the ELL form the program takes
(the benchmark's own copy of the generator), plans a probe problem with
``plan(problem, chip=attached_chip())``, and warms up with one whole solve.
Each timed call draws a fresh right-hand side from the seed on the device
(``rhs``), builds ``CGProblem.from_ell(data, cols, b, max_iters,
tol=tol**2)`` and runs ``execute(problem, plan)`` until the solver's own
convergence exit.

Every right-hand side is one of the same difficulty: in the operator's
eigenbasis its components are all of size one, with signs drawn from the
seed. CG's residuals, and so the iterations a solve needs, depend only on
the sizes of those components, so every seed and every solve does the
same work, up to rounding. A standard-normal right-hand side has the same
spectrum on average, but its component sizes vary from draw to draw, and
with them the iterations (about 450 to 500 at 256^2).

A solve whose recurrence residual is not under the tolerance at the
iteration cap counts as failed, and a run with a failed solve is not
correct. Once the window has closed, every solve's answer is judged by its
true relative residual, ``||b - A x|| / ||b||`` in float64 on the host,
with the reference's own operator.
"""
from __future__ import annotations

import functools

import numpy as np

import seeding


def build(*, config, traffic, limits, seed, devices, reference,
          control=False):
    return KrylovCell(config, traffic, limits, seed, devices, reference,
                      control)


def poisson2d_ell(side: int):
    """ELL planes of the 2-D 5-point Poisson matrix on a side x side grid:
    slot 0 the diagonal 4, then the present neighbours (-1) up, down,
    left, right; absent neighbours leave zero slots at the end."""
    n = side * side
    rows = np.arange(n)
    r, c = np.divmod(rows, side)
    data = np.zeros((n, 5), np.float32)
    cols = np.zeros((n, 5), np.int32)
    data[:, 0] = 4.0
    cols[:, 0] = rows
    slot = np.ones(n, np.int64)
    for dr, dc in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        rr, cc = r + dr, c + dc
        ok = (rr >= 0) & (rr < side) & (cc >= 0) & (cc < side)
        data[rows[ok], slot[ok]] = -1.0
        cols[rows[ok], slot[ok]] = (rr * side + cc)[ok]
        slot += ok
    return data, cols


def rhs(key, i, *, side: int):
    """The ``i``-th right-hand side of ``key``: ``S D S`` flattened, where
    ``S`` is the orthonormal sine basis of the zero-border grid (the
    Poisson operator's eigenvectors along one axis) and ``D`` a matrix of
    signs drawn from ``key`` and ``i``; its norm is ``side``."""
    import jax
    import jax.numpy as jnp
    signs = jax.random.rademacher(jax.random.fold_in(key, i), (side, side),
                                  jnp.float32)
    j = jnp.arange(1, side + 1, dtype=jnp.int32)
    phase = jnp.outer(j, j) % (2 * (side + 1))     # exact: sin has period
    s = (jnp.sqrt(2.0 / (side + 1))
         * jnp.sin(jnp.pi * phase.astype(jnp.float32) / (side + 1)))
    hi = jax.lax.Precision.HIGHEST
    return jnp.matmul(jnp.matmul(s, signs, precision=hi), s,
                      precision=hi).reshape(-1)


class KrylovCell:
    def __init__(self, config, traffic, limits, seed, devices, reference,
                 control):
        import jax
        import jax.numpy as jnp
        from repro.core.hardware import attached_chip
        from repro.exec import CGProblem, execute, plan

        self.name = config["name"]
        self.side = int(traffic["grid_side"])
        self.tol = float(config["tolerance"])
        self.max_iters = int(config["max_iters"])
        self.limits = limits
        self.reference = reference
        self.devices = list(devices)
        dev = self.devices[0]
        data, cols = poisson2d_ell(self.side)
        data, cols = jax.device_put(data, dev), jax.device_put(cols, dev)
        self.key = seeding.key(seed)
        self.rhs = jax.jit(functools.partial(rhs, side=self.side))

        def problem(b):
            return CGProblem.from_ell(data, cols, b, self.max_iters,
                                      tol=self.tol ** 2)

        self.plan = plan(problem(self.rhs(self.key, 0)), chip=attached_chip())
        if control:
            def solve(b):
                return reference.cg(b, side=self.side, tol=self.tol,
                                    max_iters=self.max_iters,
                                    dtype=jnp.bfloat16)
        else:
            def solve(b):
                return execute(problem(b), self.plan)
        self.solve = solve
        jax.block_until_ready(self.solve(self.rhs(self.key, 0)))
        self.results = []
        self.solves = 0
        self.n_failed = None

    def describe(self) -> str:
        p = self.plan
        return (f"{self.name} {self.side}^2 grid, tol {self.tol}, cap "
                f"{self.max_iters}: tier={p.tier} sync_every={p.sync_every}")

    def call(self) -> None:
        import jax
        self.solves += 1
        x, rr = self.solve(self.rhs(self.key, self.solves))
        jax.block_until_ready((x, rr))
        self.results.append((self.solves, x, rr))

    def drain(self) -> None:
        """Nothing is in flight: each solve returns when it has ended."""

    @property
    def attempted(self) -> int:
        return self.solves

    @property
    def failed(self) -> int:
        return self.n_failed

    def end_to_end(self, window_s: float, calls: int) -> dict:
        return {"krylov_solve_s": window_s / max(1, calls - self.n_failed)}

    def info(self) -> dict:
        return {"solves": self.solves}

    def finish(self) -> dict:
        """Judge every solve by its true residual, and count the solves
        that stopped at the cap over tolerance."""
        residuals, failed = [], 0
        for i, x, rr in self.results:
            b = np.asarray(self.rhs(self.key, i), np.float64)
            failed += not float(rr) < self.tol ** 2 * float(b @ b)
            residuals.append(self.reference.true_relative_residual(
                b, np.asarray(x), self.side))
        self.n_failed = failed
        self.results = []
        return {"true_rel_residual.max": {
                    "value": seeding.worst(residuals),
                    "limit": self.limits["true_rel_residual"]},
                "failed_solves": {"value": failed, "limit": 0}}
