"""Problem adapters: stencils and CG described for the unified executor.

These carry the *workload-specific* halves of what used to live in
``solvers/stencil.py`` and ``solvers/cg.py`` — the step functions, the
resident-kernel dispatch, and the distributed shard programs — behind the
:class:`repro.exec.problem.Problem` protocol, so ``repro.exec.execute``
is the single dispatch path for every tier. The solver modules remain as
thin deprecated shims over these adapters (each legacy ``run_*`` builds a
Problem + Plan and calls ``execute``).

A future workload (new stencil geometry, new sparse format, decode,
multigrid) is one more adapter here: ~50 lines, no new solver file.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import perks
from repro.core.cache_policy import (
    CacheableArray,
    cg_arrays,
    cg_arrays_for,
    stencil_shard_arrays,
)
from repro.dist.collectives import halo_exchange
from repro.dist.sharding import smap
from repro.exec.precision import PRECISIONS, dot_for
from repro.exec.problem import HaloSpec, Problem, operand_fingerprint
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.common import StencilSpec
from repro.kernels.stencil2d import row_align


def operator_fingerprint(data, cols, matrix, matvec) -> str:
    """Operand fingerprint of one sparse operator, preferring content (ELL
    planes, then the exact container's values) over identity (an opaque
    matvec callable). Folded into Krylov problem ``name``s so two
    same-size problems over different operators never alias in the
    plan/runner caches."""
    if data is not None:
        return operand_fingerprint(data, cols)
    if matrix is not None:
        return operand_fingerprint(getattr(matrix, "data", None))
    return operand_fingerprint(matvec)


def _operand_sig(a):
    """id + shape/dtype of one shared operand (batch-key component).

    Batch keys pair the id with the content fingerprint: the id catches
    in-place-distinct operators instantly, the shapes keep a recycled id
    from colliding across differently-shaped operands, and the
    fingerprint catches equal-shaped different-valued operators whose
    storage was freed and its id reused."""
    if a is None:
        return None
    shape = getattr(a, "shape", None)
    return (id(a), None if shape is None else tuple(shape),
            str(getattr(a, "dtype", None)))


# =============================================================================
# Stencil
# =============================================================================

def fusion_schedule(steps: int, fuse_steps: int) -> list[tuple[int, int]]:
    """How ``steps`` decompose into fused chunks: ``[(n_chunks, chunk_t)]``
    with one halo exchange per chunk — ceil(steps/fuse_steps) exchanges
    total. A non-dividing tail gets one narrower chunk (its halo is only
    ``radius * tail`` wide), never an overshoot."""
    full, rem = divmod(steps, fuse_steps)
    sched = []
    if full:
        sched.append((full, fuse_steps))
    if rem:
        sched.append((1, rem))
    return sched


def make_distributed_step(spec: StencilSpec, mesh: Mesh, axis: str = "data",
                          *, fuse_steps: int = 1):
    """``fuse_steps`` distributed time steps per halo exchange, inside
    shard_map over ``axis`` (leading-dim row partition).

    ``fuse_steps=1`` is the classic step: exchange ``radius`` boundary rows,
    update locally. ``fuse_steps=t`` exchanges a ``radius*t`` wide halo ONCE
    and applies the stencil t times to the extended window, which shrinks by
    ``radius`` per application — the halo region is redundantly recomputed
    instead of re-exchanged (temporal blocking, DESIGN.md §4). The global
    Dirichlet border is re-frozen after every inner application, so the
    fused step performs exactly the arithmetic of t exchanged steps
    (agreement to <= 2 ulp on real backends; see DESIGN.md §4).
    """
    r = spec.radius
    t = fuse_steps

    def local_step(x_l):
        h = x_l.shape[0]
        n = jax.lax.axis_size(axis)
        idx = jax.lax.axis_index(axis)
        H = h * n                      # global leading extent
        top, bot = halo_exchange(x_l, r * t, axis)
        w = jnp.concatenate([top, x_l, bot], axis=0)
        lo = idx * h - r * t           # global row index of w[0] (<0 at edges)
        for _ in range(t):
            L = w.shape[0]
            upd = spec.apply_rows(w, r, L - r)
            # freeze the first/last `r` rows of the *global* domain; rows
            # outside the domain (edge shards' zero-filled halo) fall under
            # the same mask and only ever feed other frozen rows.
            rows = lo + r + jnp.arange(L - 2 * r)
            frozen = (rows < r) | (rows >= H - r)
            shape = (L - 2 * r,) + (1,) * (x_l.ndim - 1)
            w = jnp.where(frozen.reshape(shape), w[r:L - r], upd)
            lo = lo + r
        return w

    pspec = P(axis, *([None] * (spec.ndim - 1)))
    return smap(local_step, mesh=mesh, in_specs=(pspec,),
                out_specs=pspec)


def stencil_distributed(x, spec: StencilSpec, steps: int, mesh: Mesh, *,
                        axis: str = "data",
                        execution: perks.Execution = perks.Execution.DEVICE_LOOP,
                        fuse_steps: int = 1):
    """Multi-chip PERKS stencil: the halo ppermute is the device-wide
    barrier; the time loop is fused (DEVICE_LOOP) or host-driven.

    ``fuse_steps=t`` issues one ``radius*t``-wide exchange per t steps —
    ceil(steps/t) collectives instead of ``steps`` — and performs the
    exact per-step arithmetic (<= 2 ulp agreement on real backends, see
    DESIGN.md §4). Requires ``radius*t`` rows per shard (the halo must
    come from the adjacent neighbour only).
    """
    t = int(fuse_steps)
    n = int(dict(mesh.shape)[axis])
    shard_rows = x.shape[0] // n
    if t < 1:
        raise ValueError(f"fuse_steps must be >= 1, got {t}")
    if spec.radius * min(t, steps) > shard_rows:
        raise ValueError(
            f"fuse_steps={t} needs a {spec.radius * t}-row halo but shards "
            f"have only {shard_rows} rows ({x.shape[0]} over {n} shards)")
    with mesh:
        for n_chunks, chunk_t in fusion_schedule(steps, t):
            step = make_distributed_step(spec, mesh, axis,
                                         fuse_steps=chunk_t)
            runner = perks.persistent(
                step, n_chunks, perks.PerksConfig(execution=execution))
            x = runner(x)
    return x


@dataclasses.dataclass(frozen=True, eq=False)
class StencilProblem(Problem):
    """Iterative stencil sweep: ``n_steps`` applications of ``spec`` to the
    domain ``x`` (outermost ``radius`` cells Dirichlet-frozen)."""

    x: jax.Array
    spec: StencilSpec
    n_steps: int

    kind = "stencil"

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"stencil_{self.spec.name}"

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        return self.x

    def step_fn(self):
        return functools.partial(kref.stencil_step, spec=self.spec)

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        row_bytes = int(math.prod(self.x.shape[1:])) * self.x.dtype.itemsize
        return stencil_shard_arrays(self.x.shape[0], row_bytes,
                                    self.spec.radius, fuse_steps=fuse_steps)

    def oracle(self):
        return kref.stencil_run(self.x, self.spec, self.n_steps)

    def halo_spec(self) -> HaloSpec:
        return HaloSpec(axis=0, halo=self.spec.radius, partitions=("rows",))

    def domain_bytes(self) -> int:
        return int(math.prod(self.x.shape)) * self.x.dtype.itemsize

    # -- batching -------------------------------------------------------------

    def payload(self):
        return self.x

    def with_payload(self, payload) -> "StencilProblem":
        return dataclasses.replace(self, x=payload)

    def batch_key(self) -> tuple:
        return ("stencil", self.spec.name, tuple(self.x.shape),
                str(self.x.dtype), self.n_steps)

    # -- tiers ----------------------------------------------------------------

    def resident_compiles(self, chip) -> bool:
        # Mosaic takes only tile-aligned leading-axis windows
        # (kernels/stencil2d._check_tiling), the domain's extent included
        return chip.interpret or self.x.shape[0] % row_align(
            self.x.shape, self.x.dtype) == 0

    def _trace_resident(self, plan) -> None:
        """Structural chunk/dma events for a resident dispatch (DESIGN.md
        §11/§12): the kernel's streaming passes happen inside ONE Pallas
        dispatch where the host-sync tracer cannot see them, so the
        adapter emits the *projected* structure — per-pass block and DMA
        counts and bytes — from the same plan the kernel executes. CI
        cross-checks these aggregates against ``gm_bytes_fused``/
        ``gm_bytes_deep``: summed streamed bytes + 2*cached bytes must
        reproduce the model."""
        from repro import obs
        tr = obs.get_tracer()
        if not tr.enabled:
            return
        H = self.x.shape[0]
        row_bytes = int(math.prod(self.x.shape[1:])) * self.x.dtype.itemsize
        cached = min(plan.cached_rows or 0, H)
        stream_rows = H - cached
        r = self.spec.radius
        for n_passes, chunk_t in fusion_schedule(self.n_steps,
                                                 plan.fuse_steps):
            if stream_rows == 0:
                blocks, rd, wr = 0, 0, 0
            else:
                blocks = -(-stream_rows // max(1, min(plan.sub_rows,
                                                      stream_rows)))
                wr = stream_rows * row_bytes
                rd = wr if plan.schedule == "deep" \
                    else wr + 2 * r * chunk_t * row_bytes
            tr.event(f"chunk:resident:{plan.schedule}", cat="chunk",
                     track="resident", passes=n_passes, fuse_steps=chunk_t,
                     blocks=blocks, stream_rows=stream_rows,
                     cached_rows=cached)
            tr.event(f"dma:resident:{plan.schedule}", cat="dma",
                     track="resident", passes=n_passes,
                     dmas_per_pass=2 * blocks, bytes_read_per_pass=rd,
                     bytes_written_per_pass=wr,
                     cached_bytes=cached * row_bytes)

    def run_resident(self, plan):
        plan.validate(radius=self.spec.radius, domain_rows=self.x.shape[0])
        cached_rows = plan.cached_rows
        if cached_rows is None:
            raise ValueError("resident stencil plan must set cached_rows "
                             "(use repro.exec.plan to build plans)")
        self._trace_resident(plan)
        if cached_rows >= self.x.shape[0]:
            return kops.stencil_resident(self.x, spec=self.spec,
                                         steps=self.n_steps,
                                         sub_rows=plan.sub_rows)
        if plan.schedule == "deep":
            return kops.stencil_perks_deep(
                self.x, spec=self.spec, steps=self.n_steps,
                cached_rows=cached_rows, sub_rows=plan.sub_rows,
                fuse_steps=plan.fuse_steps)
        return kops.stencil_perks(self.x, spec=self.spec, steps=self.n_steps,
                                  cached_rows=cached_rows,
                                  sub_rows=plan.sub_rows,
                                  fuse_steps=plan.fuse_steps)

    def run_distributed(self, plan, mesh):
        execution = (perks.Execution.HOST_LOOP
                     if plan.inner_tier == "host_loop"
                     else perks.Execution.DEVICE_LOOP)
        return stencil_distributed(
            self.x, self.spec, self.n_steps, mesh,
            axis=plan.shard_axis or "data", execution=execution,
            fuse_steps=plan.fuse_steps)


# =============================================================================
# Conjugate gradient
# =============================================================================

def fused_block_rows(n: int, cap: int = 512) -> int:
    """Largest power-of-two block size <= cap dividing n — the fused VEC
    kernel streams whole row blocks, so ``block_rows`` must divide n."""
    bm = 1
    while bm * 2 <= cap and n % (bm * 2) == 0:
        bm *= 2
    return bm


def cg_distributed(data, cols, b, iters: int, mesh: Mesh, *,
                   axis: str = "data", fuse_reductions: bool = False,
                   partition: str = "rows"):
    """Row-partitioned CG: local SpMV gathers the global p (all-gather),
    dot products psum — the collective IS the paper's device barrier.

    ``fuse_reductions=True`` is the CG face of temporal blocking
    (DESIGN.md §4; "Pipelined Iterative Solvers with Kernel Fusion",
    arXiv:1410.4054): textbook CG pays TWO dependent reduction barriers
    per iteration (p·Ap, then r'·r' after the axpys). The fused variant
    stacks FOUR simultaneous partial dots — p·Ap, r·Ap, Ap·Ap and the
    *current* r·r — into ONE chunked psum and recovers the new residual
    norm from the recurrence

        ||r'||² = ||r||² - 2α(r·Ap) + α²(Ap·Ap),   α = ||r||²/(p·Ap)

    — one synchronization per iteration instead of two. Carrying the
    recurrence alone compounds rounding noise once CG converges (β =
    noise/noise explodes the search direction — the classic pipelined-CG
    instability), so each iteration re-grounds on the true r·r that rode
    along in the same psum: the estimate's error is then one step deep
    and stays *relative* to the residual scale. Tests bound the drift vs
    textbook CG.

    ``partition="nnz"`` repacks the rows into nnz-balanced equal-shaped
    shards (``repro.sparse.partition.shard_by_nnz``) before sharding, so
    the per-iteration barrier waits for equal SpMV work instead of equal
    row counts — on a power-law graph naive equal-rows sharding leaves
    one shard with most of the nonzeros. Padded rows are algebraically
    invisible (zero data/rhs); the result is gathered back to original
    row order.
    """
    if partition == "nnz":
        from repro.sparse import shard_by_nnz
        parts = mesh.shape[axis]
        sh = shard_by_nnz(np.asarray(data), np.asarray(cols),
                          np.asarray(b), parts)
        x_pad, rr = cg_distributed(
            jnp.asarray(sh.data), jnp.asarray(sh.cols), jnp.asarray(sh.b),
            iters, mesh, axis=axis, fuse_reductions=fuse_reductions)
        return x_pad[jnp.asarray(sh.pos)], rr
    if partition != "rows":
        raise ValueError(f"partition must be 'rows' or 'nnz', got "
                         f"{partition!r}")

    def step(state):
        x, r, p, rr = state

        def local(iter_data, iter_cols, p_full, x_l, r_l, p_l, rr_s):
            from repro.kernels.ref import _safe_div
            ap_l = jnp.sum(iter_data * p_full[iter_cols], axis=1)
            if fuse_reductions:
                dots = jax.lax.psum(
                    jnp.stack([jnp.vdot(p_l, ap_l), jnp.vdot(r_l, ap_l),
                               jnp.vdot(ap_l, ap_l), jnp.vdot(r_l, r_l)]),
                    axis)
                pap, rap, apap, rr_true = dots[0], dots[1], dots[2], dots[3]
                alpha = _safe_div(rr_true, pap)
                x_l = x_l + alpha * p_l
                r_l = r_l - alpha * ap_l
                rr_new = jnp.maximum(
                    rr_true - 2.0 * alpha * rap + alpha * alpha * apap, 0.0)
                beta = _safe_div(rr_new, rr_true)
                p_l = r_l + beta * p_l
                return x_l, r_l, p_l, rr_new
            else:
                pap = jax.lax.psum(jnp.vdot(p_l, ap_l), axis)
                alpha = _safe_div(rr_s, pap)
                x_l = x_l + alpha * p_l
                r_l = r_l - alpha * ap_l
                rr_new = jax.lax.psum(jnp.vdot(r_l, r_l), axis)
            beta = _safe_div(rr_new, rr_s)
            p_l = r_l + beta * p_l
            return x_l, r_l, p_l, rr_new

        return smap(
            local, mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(), P(axis), P(axis),
                      P(axis), P()),
            out_specs=(P(axis), P(axis), P(axis), P()),
        )(data, cols, p, x, r, p, rr)

    state = (jnp.zeros_like(b), b, b, jnp.vdot(b, b))
    with mesh:
        state = perks.device_loop(step, iters)(state)
    return state[0], state[3]


@dataclasses.dataclass(frozen=True, eq=False)
class CGProblem(Problem):
    """Conjugate gradient on an SPD operator.

    Two operator forms: block-ELL planes (``data``/``cols`` — the legacy
    path, required for the fused resident kernel and the distributed
    tier) and/or an opaque ``matvec`` callable (e.g. the SELL-C-σ
    operator), which takes precedence for the loop tiers. ``matrix`` may
    carry any ``repro.sparse`` container so the cache planner ranks A by
    its **true** nnz rather than padded slots.
    """

    b: jax.Array
    n_steps: int
    data: Optional[jax.Array] = None
    cols: Optional[jax.Array] = None
    matvec: Optional[Callable[[jax.Array], jax.Array]] = None
    matrix: Any = None
    tol: Optional[float] = None
    precision: str = "uniform"

    kind = "cg"

    def __post_init__(self):
        if self.matvec is None and self.data is None:
            raise ValueError("CGProblem needs ELL planes (data, cols) or a "
                             "matvec callable")
        if self.precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, "
                             f"got {self.precision!r}")

    @classmethod
    def from_ell(cls, data, cols, b, iters: int, *, matrix=None,
                 tol: Optional[float] = None) -> "CGProblem":
        return cls(b=b, n_steps=iters, data=data, cols=cols, matrix=matrix,
                   tol=tol)

    @classmethod
    def from_matvec(cls, matvec, b, iters: int, *, matrix=None,
                    tol: Optional[float] = None) -> "CGProblem":
        return cls(b=b, n_steps=iters, matvec=matvec, matrix=matrix, tol=tol)

    @property
    def name(self) -> str:  # type: ignore[override]
        fp = operator_fingerprint(self.data, self.cols, self.matrix,
                                  self.matvec)
        return f"cg_n{self.b.shape[0]}_{fp}"

    # -- protocol -------------------------------------------------------------

    def initial_state(self):
        return (jnp.zeros_like(self.b), self.b, self.b,
                jnp.vdot(self.b, self.b))

    def step_fn(self):
        dot = dot_for(self.precision)
        if self.matvec is not None:
            mv = self.matvec
        else:
            mv = functools.partial(kref.spmv_ell, self.data, self.cols)
        return lambda s: kref.cg_iteration_matvec(s, mv, dot=dot)

    def finalize(self, state):
        return state[0], state[3]

    def convergence(self):
        # relative residual: ||r_k||^2 < tol * ||b||^2. The predicate is
        # shared by every instance of the operator's batch key; only the
        # threshold (a per-instance scalar derived from b) varies, so the
        # batched tier checks all lanes in one stacked reduction.
        if self.tol is None:
            return None
        thresh = self.tol * jnp.vdot(self.b, self.b)
        return (lambda s, th: s[3] < th), thresh

    def cacheable_arrays(self, *, fuse_steps: int = 1) -> Sequence[CacheableArray]:
        if self.matrix is not None:
            return cg_arrays_for(self.matrix)
        n = self.b.shape[0]
        if self.data is not None:
            nnz = int(self.data.shape[0]) * int(self.data.shape[1])
        else:
            nnz = 0
        return cg_arrays(n, nnz, self.b.dtype.itemsize)

    def oracle(self):
        if self.data is None:
            raise NotImplementedError("CG oracle needs ELL planes")
        return kref.cg_run(self.data, self.cols, self.b, self.n_steps)

    def halo_spec(self) -> HaloSpec:
        return HaloSpec(axis=0, halo=0, partitions=("rows", "nnz"))

    # -- batching -------------------------------------------------------------

    def payload(self):
        return self.b

    def with_payload(self, payload) -> "CGProblem":
        return dataclasses.replace(self, b=payload)

    def with_precision(self, precision: str) -> "CGProblem":
        if precision == self.precision:
            return self
        return dataclasses.replace(self, precision=precision)

    def batch_key(self) -> tuple:
        # instances share one batch iff they solve against the SAME
        # operator (A is shared across the dispatch, only the right-hand
        # sides are stacked) with the same iteration budget. The content
        # fingerprint + per-operand id/shape sigs together prevent
        # aliasing between different same-shaped operators even across
        # id() reuse (plan caches additionally pin their operands —
        # solver_service.py).
        fp = operator_fingerprint(self.data, self.cols, self.matrix,
                                  self.matvec)
        return ("cg", fp, _operand_sig(self.data), _operand_sig(self.cols),
                id(self.matvec), id(self.matrix), tuple(self.b.shape),
                str(self.b.dtype), self.n_steps, self.tol, self.precision)

    def array_scales_with_batch(self, name: str) -> bool:
        # the matrix is shared by every instance of a batch; the Krylov
        # vectors are per-instance (DESIGN.md §8)
        return name != "A"

    # -- tiers ----------------------------------------------------------------

    def resident_compiles(self, chip) -> bool:
        # the fused kernels gather x by column index, which Mosaic does
        # not lower for a TPU (kernels/spmv_ell.py)
        return chip.interpret

    def run_resident(self, plan):
        if self.data is None:
            raise NotImplementedError(
                "fused CG kernel needs ELL planes (matvec-only problem)")
        if self.precision != "uniform":
            raise NotImplementedError(
                "mixed precision is a loop-tier dimension (the fused "
                "kernel reduces in storage dtype)")
        resident = (plan.policy or "MIX") in ("MAT", "MIX")
        block_rows = plan.block_rows or 256
        x, rr = kops.cg(self.data, self.cols, self.b, iters=self.n_steps,
                        resident_matrix=resident, block_rows=block_rows)
        return x, rr[0]

    def run_distributed(self, plan, mesh):
        if self.data is None:
            raise NotImplementedError(
                "distributed CG needs ELL planes (matvec-only problem)")
        if self.precision != "uniform":
            raise NotImplementedError(
                "mixed precision is a loop-tier dimension")
        if plan.s_step > 1:
            if plan.fuse_reductions or plan.partition == "nnz":
                raise ValueError(
                    "s_step > 1 replaces the per-iteration reductions "
                    "entirely; it composes with neither fuse_reductions "
                    "nor partition='nnz'")
            from repro.exec.krylov import cg_sstep_distributed
            return cg_sstep_distributed(
                self.data, self.cols, self.b, self.n_steps, mesh,
                s=plan.s_step, axis=plan.shard_axis or "data")
        return cg_distributed(
            self.data, self.cols, self.b, self.n_steps, mesh,
            axis=plan.shard_axis or "data",
            fuse_reductions=plan.fuse_reductions,
            partition=plan.partition)
