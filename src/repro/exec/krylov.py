"""The pipelined Krylov family: BiCGStab, GMRES(m), s-step CG.

The paper's CG result (§V-C) generalizes to any Krylov method whose
iteration is a step function over on-chip-cacheable vectors; this module
is that generalization, following "Pipelined Iterative Solvers with
Kernel Fusion" (arXiv:1410.4054) for the reduction restructuring:

* :class:`BiCGStabProblem` — the nonsymmetric workhorse. Two SpMVs and
  five reductions per iteration; the distributed tier groups them into
  THREE psums (``fuse_reductions=True``) by stacking the independent
  <t,s>/<t,t>/<s,s> dots into one chunked collective and recovering the
  residual norm from the omega-recurrence
  ``||r'||^2 = <s,s> - 2w<t,s> + w^2<t,t>`` — the BiCGStab face of the
  fused-reduction CG already in ``adapters.cg_distributed``.
* :class:`GMRESProblem` — restarted GMRES(m). One step = one restart
  cycle; the Arnoldi basis V is a first-class cacheable array the
  planner can pin on-chip (``cache_policy.gmres_arrays``), which is the
  PERKS story for GMRES: the basis never round-trips HBM within a cycle.
* s-step CG (``cg_sstep_run`` / ``cg_sstep_distributed``) — the
  communication-avoiding variant of the distributed tier: build the
  monomial bases P = [p, Ap, ..., A^s p], R = [r, Ar, ..., A^{s-1} r],
  form the Gram matrix G = V V^T with ONE psum, then advance s
  iterations entirely in (2s+1)-dimensional coefficient space. One
  collective per s iterations — ceil(iters/s) total, asserted by jaxpr
  psum counting in tests — at the price of 2s-1 SpMVs per s iterations
  (redundant compute for fewer syncs, the same trade temporal blocking
  makes for stencils). ``Plan.s_step`` selects it.

All three run through the existing ``Problem -> plan -> execute`` path,
so ``BatchedProblem``/``SolverService`` serve them with zero new code.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import perks
from repro.core.cache_policy import (
    CacheableArray,
    bicgstab_arrays,
    bicgstab_arrays_for,
    gmres_arrays,
    gmres_arrays_for,
)
from repro.dist.sharding import smap
from repro.exec.adapters import (
    CGProblem,
    _operand_sig,
    fused_block_rows,
    fusion_schedule,
    krylov_step,
    operator_fingerprint,
)
from repro.exec.precision import PRECISIONS
from repro.exec.problem import HaloSpec, Problem
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.ref import _safe_div


# =============================================================================
# BiCGStab
# =============================================================================

def bicgstab_distributed(data, cols, b, iters: int, mesh: Mesh, *,
                         axis: str = "data", fuse_reductions: bool = True):
    """Row-partitioned BiCGStab: each SpMV all-gathers the direction
    vector; the dots psum. ``fuse_reductions=False`` is the textbook
    schedule — FIVE dependent psums per iteration (rho, rhat.v, t.s, t.t,
    r'.r'). ``fuse_reductions=True`` stacks the three simultaneous
    stabilization dots into ONE chunked psum and recovers ||r'||^2 from
    the omega-recurrence (re-grounded each iteration on the <s,s> that
    rode along in the same psum) — THREE psums per iteration, the
    1410.4054 pipelining applied to BiCGStab. Tests bound the drift vs
    the textbook schedule."""

    def step(state):
        x, r, rhat, p, v, rho, alpha, omega, rr = state

        def local(data_l, cols_l, x_l, r_l, rhat_l, p_l, v_l,
                  rho_s, alpha_s, omega_s, rr_s):
            def mv(q_l):
                q = jax.lax.all_gather(q_l, axis, tiled=True)
                return jnp.sum(data_l * q[cols_l], axis=1)

            rho_new = jax.lax.psum(jnp.vdot(rhat_l, r_l), axis)
            beta = _safe_div(rho_new, rho_s) * _safe_div(alpha_s, omega_s)
            p_l = r_l + beta * (p_l - omega_s * v_l)
            v_l = mv(p_l)
            alpha_n = _safe_div(rho_new,
                                jax.lax.psum(jnp.vdot(rhat_l, v_l), axis))
            s_l = r_l - alpha_n * v_l
            t_l = mv(s_l)
            if fuse_reductions:
                dots = jax.lax.psum(
                    jnp.stack([jnp.vdot(t_l, s_l), jnp.vdot(t_l, t_l),
                               jnp.vdot(s_l, s_l)]), axis)
                ts, tt, ss = dots[0], dots[1], dots[2]
                omega_n = _safe_div(ts, tt)
                rr_new = jnp.maximum(
                    ss - 2.0 * omega_n * ts + omega_n * omega_n * tt, 0.0)
                x_l = x_l + alpha_n * p_l + omega_n * s_l
                r_l = s_l - omega_n * t_l
            else:
                ts = jax.lax.psum(jnp.vdot(t_l, s_l), axis)
                tt = jax.lax.psum(jnp.vdot(t_l, t_l), axis)
                omega_n = _safe_div(ts, tt)
                x_l = x_l + alpha_n * p_l + omega_n * s_l
                r_l = s_l - omega_n * t_l
                rr_new = jax.lax.psum(jnp.vdot(r_l, r_l), axis)
            return (x_l, r_l, rhat_l, p_l, v_l,
                    rho_new, alpha_n, omega_n, rr_new)

        return smap(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None)) + (P(axis),) * 5
            + (P(),) * 4,
            out_specs=(P(axis),) * 5 + (P(),) * 4,
        )(data, cols, x, r, rhat, p, v, rho, alpha, omega, rr)

    state = kref.bicgstab_initial_state(b)
    with mesh:
        state = perks.device_loop(step, iters)(state)
    return state[0], state[8]


@dataclasses.dataclass(frozen=True, eq=False)
class BiCGStabProblem(Problem):
    """BiCGStab on a (possibly nonsymmetric) operator.

    Same operator forms as :class:`~repro.exec.adapters.CGProblem`:
    block-ELL planes (required for the fused resident kernel and the
    distributed tier) and/or an opaque ``matvec``; ``matrix`` carries the
    exact container so the planner ranks A by true nnz.
    """

    b: jax.Array
    n_steps: int
    data: Optional[jax.Array] = None
    cols: Optional[jax.Array] = None
    matvec: Optional[Callable[[jax.Array], jax.Array]] = None
    matrix: Any = None
    tol: Optional[float] = None
    precision: str = "uniform"

    kind = "bicgstab"

    def __post_init__(self):
        if self.matvec is None and self.data is None:
            raise ValueError("BiCGStabProblem needs ELL planes (data, cols) "
                             "or a matvec callable")
        if self.precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {self.precision!r}")

    @classmethod
    def from_ell(cls, data, cols, b, iters: int, *, matrix=None,
                 tol: Optional[float] = None) -> "BiCGStabProblem":
        return cls(b=b, n_steps=iters, data=data, cols=cols, matrix=matrix,
                   tol=tol)

    @classmethod
    def from_matvec(cls, matvec, b, iters: int, *, matrix=None,
                    tol: Optional[float] = None) -> "BiCGStabProblem":
        return cls(b=b, n_steps=iters, matvec=matvec, matrix=matrix, tol=tol)

    @property
    def name(self) -> str:  # type: ignore[override]
        fp = operator_fingerprint(self.data, self.cols, self.matrix,
                                  self.matvec)
        return f"bicgstab_n{self.b.shape[0]}_{fp}"

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        return kref.bicgstab_initial_state(self.b)

    def step(self):
        it, prec = kref.bicgstab_iteration_matvec, self.precision
        if self.matvec is not None:
            return krylov_step(it, None, prec, matvec=self.matvec), ()
        return krylov_step(it, "ell", prec), (self.data, self.cols)

    def finalize(self, state):
        return state[0], state[8]

    def convergence(self):
        if self.tol is None:
            return None
        thresh = self.tol * jnp.vdot(self.b, self.b)
        return (lambda s, th: s[8] < th), thresh

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        if self.matrix is not None:
            return bicgstab_arrays_for(self.matrix)
        n = self.b.shape[0]
        nnz = (int(self.data.shape[0]) * int(self.data.shape[1])
               if self.data is not None else 0)
        return bicgstab_arrays(n, nnz, self.b.dtype.itemsize)

    def oracle(self):
        if self.data is None:
            raise NotImplementedError("BiCGStab oracle needs ELL planes")
        return kref.bicgstab_run(self.data, self.cols, self.b, self.n_steps)

    def halo_spec(self) -> HaloSpec:
        return HaloSpec(axis=0, halo=0, partitions=("rows",))

    # -- batching / precision -------------------------------------------------

    def payload(self):
        return self.b

    def with_payload(self, payload) -> "BiCGStabProblem":
        return dataclasses.replace(self, b=payload)

    def with_precision(self, precision: str) -> "BiCGStabProblem":
        if precision == self.precision:
            return self
        return dataclasses.replace(self, precision=precision)

    def batch_key(self) -> tuple:
        fp = operator_fingerprint(self.data, self.cols, self.matrix,
                                  self.matvec)
        return ("bicgstab", fp, _operand_sig(self.data),
                _operand_sig(self.cols), id(self.matvec),
                tuple(self.b.shape), str(self.b.dtype), self.n_steps,
                self.tol, self.precision)

    def array_scales_with_batch(self, name: str) -> bool:
        return name != "A"

    # -- tiers ----------------------------------------------------------------

    resident_compiles = CGProblem.resident_compiles

    def run_resident(self, plan):
        if self.data is None:
            raise NotImplementedError(
                "fused BiCGStab kernel needs ELL planes (matvec-only "
                "problem)")
        if self.precision != "uniform":
            raise NotImplementedError(
                "mixed precision is a loop-tier dimension (the fused "
                "kernel reduces in storage dtype)")
        resident = (plan.policy or "MIX") in ("MAT", "MIX")
        block_rows = plan.block_rows or 256
        x, rr = kops.bicgstab(self.data, self.cols, self.b,
                              iters=self.n_steps, resident_matrix=resident,
                              block_rows=block_rows)
        return x, rr[0]

    def run_distributed(self, plan, mesh):
        if self.data is None:
            raise NotImplementedError(
                "distributed BiCGStab needs ELL planes (matvec-only "
                "problem)")
        if self.precision != "uniform":
            raise NotImplementedError(
                "mixed precision is a loop-tier dimension")
        return bicgstab_distributed(
            self.data, self.cols, self.b, self.n_steps, mesh,
            axis=plan.shard_axis or "data",
            fuse_reductions=plan.fuse_reductions)


# =============================================================================
# GMRES(m)
# =============================================================================

def gmres_distributed(data, cols, b, cycles: int, m: int, mesh: Mesh, *,
                      axis: str = "data"):
    """Row-partitioned GMRES(m): the Arnoldi basis is row-partitioned with
    the operator, every CGS2 projection psums its (m+1)-vector of partial
    products, and the small least-squares solve is replicated per chip.
    3m+2 psums per cycle (beta, two projection rounds + one norm per
    inner step, final residual)."""

    def cycle(state):
        x, rr = state

        def local(data_l, cols_l, b_l, x_l, rr_s):
            def mv(q_l):
                q = jax.lax.all_gather(q_l, axis, tiled=True)
                return jnp.sum(data_l * q[cols_l], axis=1)

            pdot = lambda u, v: jax.lax.psum(jnp.vdot(u, v), axis)
            pred = lambda z: jax.lax.psum(z, axis)
            return kref.gmres_cycle_matvec((x_l, rr_s), mv, b_l, m,
                                           dot=pdot, basis_reduce=pred)

        return smap(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(axis), P(axis), P()),
            out_specs=(P(axis), P()),
        )(data, cols, b, x, rr)

    state = (jnp.zeros_like(b), jnp.vdot(b, b))
    with mesh:
        state = perks.device_loop(cycle, cycles)(state)
    return state


def _gmres_cycle(state, mv, *, dot, m: int):
    """One restart cycle over the state ``(x, rr, b)``."""
    x, rr, b = state
    x, rr = kref.gmres_cycle_matvec((x, rr), mv, b, m, dot=dot)
    return (x, rr, b)


@dataclasses.dataclass(frozen=True, eq=False)
class GMRESProblem(Problem):
    """Restarted GMRES(m); one executor step = one restart cycle.

    ``n_steps`` counts cycles (m inner Arnoldi steps each). The basis V
    — (m+1) x n — is exposed to the cache planner as a first-class
    cacheable array; when it fits on-chip the resident tier runs the
    whole cycle in one fused kernel with V pinned in VMEM
    (``kernels/krylov_fused.gmres_cycle_fused``).

    The right-hand side rides in the loop state (``(x, rr, b)``) rather
    than a closure, so the vmapped batched tier gives every lane its own
    b — the step function itself is payload-free.
    """

    b: jax.Array
    n_steps: int
    m: int = 16
    data: Optional[jax.Array] = None
    cols: Optional[jax.Array] = None
    matvec: Optional[Callable[[jax.Array], jax.Array]] = None
    matrix: Any = None
    tol: Optional[float] = None
    precision: str = "uniform"

    kind = "gmres"

    def __post_init__(self):
        if self.matvec is None and self.data is None:
            raise ValueError("GMRESProblem needs ELL planes (data, cols) or "
                             "a matvec callable")
        if self.m < 1:
            raise ValueError(f"m must be >= 1, got {self.m}")
        if self.precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {self.precision!r}")

    @classmethod
    def from_ell(cls, data, cols, b, cycles: int, *, m: int = 16,
                 matrix=None, tol: Optional[float] = None) -> "GMRESProblem":
        return cls(b=b, n_steps=cycles, m=m, data=data, cols=cols,
                   matrix=matrix, tol=tol)

    @classmethod
    def from_matvec(cls, matvec, b, cycles: int, *, m: int = 16,
                    matrix=None, tol: Optional[float] = None) -> "GMRESProblem":
        return cls(b=b, n_steps=cycles, m=m, matvec=matvec, matrix=matrix,
                   tol=tol)

    @property
    def name(self) -> str:  # type: ignore[override]
        fp = operator_fingerprint(self.data, self.cols, self.matrix,
                                  self.matvec)
        return f"gmres_n{self.b.shape[0]}_m{self.m}_{fp}"

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        return (jnp.zeros_like(self.b), jnp.vdot(self.b, self.b), self.b)

    def step(self):
        prec, m = self.precision, self.m
        if self.matvec is not None:
            return krylov_step(_gmres_cycle, None, prec, matvec=self.matvec,
                               m=m), ()
        return krylov_step(_gmres_cycle, "ell", prec, m=m), (self.data,
                                                            self.cols)

    def finalize(self, state):
        return state[0], state[1]

    def convergence(self):
        if self.tol is None:
            return None
        thresh = self.tol * jnp.vdot(self.b, self.b)
        return (lambda s, th: s[1] < th), thresh

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        if self.matrix is not None:
            return gmres_arrays_for(self.matrix, self.m)
        n = self.b.shape[0]
        nnz = (int(self.data.shape[0]) * int(self.data.shape[1])
               if self.data is not None else 0)
        return gmres_arrays(n, self.m, nnz, self.b.dtype.itemsize)

    def oracle(self):
        if self.data is None:
            raise NotImplementedError("GMRES oracle needs ELL planes")
        return kref.gmres_run(self.data, self.cols, self.b, self.n_steps,
                              self.m)

    def halo_spec(self) -> HaloSpec:
        return HaloSpec(axis=0, halo=0, partitions=("rows",))

    # -- batching / precision -------------------------------------------------

    def payload(self):
        return self.b

    def with_payload(self, payload) -> "GMRESProblem":
        return dataclasses.replace(self, b=payload)

    def with_precision(self, precision: str) -> "GMRESProblem":
        if precision == self.precision:
            return self
        return dataclasses.replace(self, precision=precision)

    def batch_key(self) -> tuple:
        fp = operator_fingerprint(self.data, self.cols, self.matrix,
                                  self.matvec)
        return ("gmres", fp, _operand_sig(self.data),
                _operand_sig(self.cols), id(self.matvec),
                tuple(self.b.shape), str(self.b.dtype), self.n_steps,
                self.m, self.tol, self.precision)

    def array_scales_with_batch(self, name: str) -> bool:
        return name != "A"

    # -- tiers ----------------------------------------------------------------

    resident_compiles = CGProblem.resident_compiles

    def run_resident(self, plan):
        if self.data is None:
            raise NotImplementedError(
                "fused GMRES cycle kernel needs ELL planes (matvec-only "
                "problem)")
        if self.precision != "uniform":
            raise NotImplementedError(
                "mixed precision is a loop-tier dimension")
        x = jnp.zeros_like(self.b)
        for _ in range(self.n_steps):
            V, H, beta = kops.gmres_cycle(self.data, self.cols, x, self.b,
                                          m=self.m)
            e1 = jnp.zeros((self.m + 1,), self.b.dtype).at[0].set(beta[0])
            y, _, _, _ = jnp.linalg.lstsq(H, e1)
            x = x + y @ V[:self.m]
        r = self.b - kref.spmv_ell(self.data, self.cols, x)
        return x, jnp.vdot(r, r)

    def run_distributed(self, plan, mesh):
        if self.data is None:
            raise NotImplementedError(
                "distributed GMRES needs ELL planes (matvec-only problem)")
        if self.precision != "uniform":
            raise NotImplementedError(
                "mixed precision is a loop-tier dimension")
        x, rr = gmres_distributed(
            self.data, self.cols, self.b, self.n_steps, self.m, mesh,
            axis=plan.shard_axis or "data")
        return x, rr


# =============================================================================
# s-step (communication-avoiding) CG
# =============================================================================

def _sstep_shift(s: int) -> np.ndarray:
    """The (2s+1)x(2s+1) shift matrix T of the monomial basis
    V = [P_0..P_s, R_0..R_{s-1}]: T maps a coefficient vector c to the
    coefficients of A (V^T c) — columns 0..s-1 shift within the P block,
    columns s+1..2s-1 within the R block (the last member of each block
    has no A-image in the basis, and is never multiplied: the recurrence
    only applies T to vectors with zero weight there)."""
    d = 2 * s + 1
    T = np.zeros((d, d), np.float64)
    for k in range(s):
        T[k + 1, k] = 1.0
    for k in range(s - 1):
        T[s + 2 + k, s + 1 + k] = 1.0
    return T


def sstep_block(x, r, p, rr, *, s: int, matvec, psum=None, dtype=None):
    """Advance s CG iterations with ONE global reduction.

    Builds the monomial bases (2s-1 SpMVs), forms the Gram matrix
    G = V V^T in a single ``psum`` (the one collective), then runs the s
    scalar recurrences in coefficient space: with a_j, b_j, c_j the
    coefficients of p_j, r_j, x_j - x_0 in the basis,

        alpha_j = (b_j G b_j) / (a_j G T a_j)
        c_{j+1} = c_j + alpha_j a_j
        b_{j+1} = b_j - alpha_j T a_j
        beta_j  = (b_{j+1} G b_{j+1}) / (b_j G b_j)
        a_{j+1} = b_{j+1} + beta_j a_j

    — exactly textbook CG in exact arithmetic (tests assert matched-
    cadence equivalence vs ``ref.cg_run``). Returns (x, r, p, rr).
    """
    red = (lambda z: z) if psum is None else psum
    dtype = dtype or x.dtype
    Ps = [p]
    for _ in range(s):
        Ps.append(matvec(Ps[-1]))
    Rs = [r]
    for _ in range(s - 1):
        Rs.append(matvec(Rs[-1]))
    V = jnp.stack(Ps + Rs)                       # (2s+1, n_local)
    G = red(V @ V.T)                             # the ONE collective
    T = jnp.asarray(_sstep_shift(s), dtype)

    d = 2 * s + 1
    a = jnp.zeros((d,), dtype).at[0].set(1.0)    # p_0 = P_0
    bv = jnp.zeros((d,), dtype).at[s + 1].set(1.0)   # r_0 = R_0
    c = jnp.zeros((d,), dtype)                   # x_0 - x_0 = 0
    rr_c = rr
    for _ in range(s):
        w = T @ a
        alpha = _safe_div(rr_c, a @ (G @ w))
        c = c + alpha * a
        bv = bv - alpha * w
        rr_new = jnp.maximum(bv @ (G @ bv), 0.0)
        beta = _safe_div(rr_new, rr_c)
        a = bv + beta * a
        rr_c = rr_new
    return x + c @ V, bv @ V, a @ V, rr_c


def cg_sstep_run(data, cols, b, iters: int, *, s: int = 4):
    """Single-device s-step CG on ELL planes (the matched-cadence
    equivalence oracle for the distributed variant; compare against
    ``ref.cg_run`` at the same total iteration count). A non-dividing
    tail runs one narrower block (``fusion_schedule`` semantics)."""
    mv = functools.partial(kref.spmv_ell, data, cols)
    state = (jnp.zeros_like(b), b, b, jnp.vdot(b, b))
    for n_chunks, chunk_s in fusion_schedule(iters, s):
        def step(st, _cs=chunk_s):
            return sstep_block(*st, s=_cs, matvec=mv, dtype=b.dtype)
        state = perks.device_loop(step, n_chunks)(state)
    return state[0], state[3]


def cg_sstep_distributed(data, cols, b, iters: int, mesh: Mesh, *,
                         s: int = 4, axis: str = "data"):
    """Distributed s-step CG: ONE psum (the Gram matrix) per s iterations
    — ceil(iters/s) collectives for the whole solve, vs one per iteration
    for the pipelined variant and two for textbook. The SpMVs still
    all-gather their operand (2s-1 gathers per block); what s-step folds
    is the *latency-bound reduction* barrier, which is the term that
    scales with mesh size."""

    def make_step(chunk_s):
        def step(state):
            x, r, p, rr = state

            def local(data_l, cols_l, x_l, r_l, p_l, rr_s):
                def mv(q_l):
                    q = jax.lax.all_gather(q_l, axis, tiled=True)
                    return jnp.sum(data_l * q[cols_l], axis=1)

                return sstep_block(
                    x_l, r_l, p_l, rr_s, s=chunk_s, matvec=mv,
                    psum=lambda z: jax.lax.psum(z, axis), dtype=b.dtype)

            return smap(
                local, mesh=mesh,
                in_specs=(P(axis, None), P(axis, None), P(axis), P(axis),
                          P(axis), P()),
                out_specs=(P(axis), P(axis), P(axis), P()),
            )(data, cols, x, r, p, rr)

        return step

    state = (jnp.zeros_like(b), b, b, jnp.vdot(b, b))
    with mesh:
        for n_chunks, chunk_s in fusion_schedule(iters, s):
            state = perks.device_loop(make_step(chunk_s), n_chunks)(state)
    return state[0], state[3]
