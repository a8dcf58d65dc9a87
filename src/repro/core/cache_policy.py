"""The PERKS caching policy (paper §III-B), made explicit and testable.

Given the set of arrays an iterative solver touches every time step, and an
on-chip cache budget (VMEM on TPU), decide *what* to keep resident across
time steps. The paper's ordering, reproduced here:

  1. Data with **no inter-block dependency** (interior of a thread block /
     interior of a chip's shard): caching saves one load *and* one store
     per step.
  2. Data **with inter-block dependency** (shard boundary read by
     neighbours): caching saves one load per step — the store to main
     memory must still happen so neighbours can read it.
  3. **Halo** data (owned by neighbours, refreshed every step): caching
     saves nothing; never cached.

For multi-array solvers (CG), arrays are ranked by traffic saved per byte
cached, e.g. residual vector r (3 loads + 1 store per element per step)
outranks matrix A (1 load) — paper: "ideal cache priority is r > A".

The planner is a greedy fractional knapsack on traffic density, which is
optimal here because arrays are arbitrarily divisible (we can cache any
prefix of an array) — matching the paper's finding (§VI-G3) that "a simple
greedy approach ... gives mostly the best performance".
"""
from __future__ import annotations

import dataclasses
from typing import Sequence


@dataclasses.dataclass(frozen=True)
class CacheableArray:
    """One array (or domain region) a solver touches each time step.

    loads/stores are *main-memory accesses per byte per time step* in the
    non-cached execution. ``inter_block_dep`` marks shard-boundary data whose
    stores cannot be elided (neighbours read them); ``is_halo`` marks
    neighbour-owned data that is refreshed every step.
    """

    name: str
    bytes: int
    loads_per_step: float = 1.0
    stores_per_step: float = 1.0
    inter_block_dep: bool = False
    is_halo: bool = False

    def traffic_saved_per_byte(self) -> float:
        """Main-memory bytes avoided per cached byte per time step."""
        if self.is_halo:
            return 0.0
        if self.inter_block_dep:
            # the store must still reach main memory for neighbours
            return self.loads_per_step
        return self.loads_per_step + self.stores_per_step


@dataclasses.dataclass(frozen=True)
class CacheAssignment:
    array: CacheableArray
    cached_bytes: int

    @property
    def fraction(self) -> float:
        return self.cached_bytes / self.array.bytes if self.array.bytes else 0.0


@dataclasses.dataclass(frozen=True)
class CachePlan:
    assignments: tuple[CacheAssignment, ...]
    budget_bytes: int

    @property
    def cached_bytes(self) -> int:
        return sum(a.cached_bytes for a in self.assignments)

    @property
    def traffic_saved_per_step(self) -> float:
        """Total main-memory bytes avoided per time step under this plan."""
        return sum(
            a.cached_bytes * a.array.traffic_saved_per_byte()
            for a in self.assignments
        )

    def fraction_of(self, name: str) -> float:
        for a in self.assignments:
            if a.array.name == name:
                return a.fraction
        return 0.0


def plan_caching(
    arrays: Sequence[CacheableArray],
    budget_bytes: int,
    *,
    reserve_bytes: int = 0,
) -> CachePlan:
    """Greedy fractional-knapsack cache plan (the paper's policy).

    ``reserve_bytes`` holds back on-chip memory the kernel itself needs
    (compute tile, double buffers) — the analogue of the occupancy-reduction
    analysis that determines how much register/shared memory is *actually*
    free for caching.
    """
    budget = max(0, budget_bytes - reserve_bytes)
    # stable on ties: preserve caller's order (paper lists r before p/x)
    ranked = [
        a
        for _, _, a in sorted(
            (-a.traffic_saved_per_byte(), i, a)
            for i, a in enumerate(arrays)
            if a.traffic_saved_per_byte() > 0.0
        )
    ]
    assignments = []
    remaining = budget
    for arr in ranked:
        take = min(arr.bytes, remaining)
        if take <= 0:
            break
        assignments.append(CacheAssignment(arr, take))
        remaining -= take
    return CachePlan(tuple(assignments), budget)


def stencil_arrays(
    interior_bytes: int,
    boundary_bytes: int,
    halo_bytes: int,
) -> list[CacheableArray]:
    """Cacheable regions of a stencil shard, per paper §III-B1."""
    return [
        CacheableArray("interior", interior_bytes, 1.0, 1.0, inter_block_dep=False),
        CacheableArray("boundary", boundary_bytes, 1.0, 1.0, inter_block_dep=True),
        CacheableArray("halo", halo_bytes, 1.0, 0.0, is_halo=True),
    ]


def stencil_shard_arrays(
    shard_rows: int,
    row_bytes: int,
    radius: int,
    *,
    fuse_steps: int = 1,
) -> list[CacheableArray]:
    """Cacheable regions of a row-partitioned shard under temporal blocking.

    With ``fuse_steps`` = t steps fused per halo exchange (DESIGN.md §4),
    the ring neighbours read — and the halo they send back — widens from
    ``radius`` to ``radius * t`` rows per side. The boundary region (stores
    must still reach main memory) and the never-cached halo grow with t,
    shrinking the fully-elidable interior: the t-dependent wider uncached
    ring of the generalized Eq. 5.
    """
    ring = min(shard_rows, 2 * radius * fuse_steps)   # both sides
    interior = shard_rows - ring
    return stencil_arrays(interior * row_bytes, ring * row_bytes,
                          2 * radius * fuse_steps * row_bytes)


def gm_bytes_fused(
    n_steps: int,
    domain_bytes: int,
    cached_bytes: int,
    *,
    row_bytes: int,
    radius: int,
    fuse_steps: int,
) -> float:
    """Eq. 5 generalized to temporal blocking.

    The uncached region round-trips main memory once per *pass* of t fused
    steps instead of once per step, at the price of a 2*r*t-row window
    overlap re-read per pass:

        A_gm = ceil(N/t) * (2*D_uncached + 2*r*t*row_bytes) + 2*D_cached

    ``fuse_steps=1`` recovers Eq. 5 plus the per-step halo re-read the
    paper accounts separately in Eq. 9. Note the overlap term is constant
    per *step* (2*r*row_bytes amortized), so deeper fusion is pure win on
    traffic until the wider working set eats the VMEM cache budget.
    """
    t = fuse_steps
    passes = -(-n_steps // t)
    uncached = max(0, domain_bytes - cached_bytes)
    overlap = 2 * radius * t * row_bytes if uncached else 0
    return passes * (2.0 * uncached + overlap) + 2.0 * cached_bytes


def gm_bytes_deep(
    n_steps: int,
    domain_bytes: int,
    cached_bytes: int,
    *,
    fuse_steps: int,
) -> float:
    """Eq. 5 under DEEP temporal blocking (arXiv:2306.03336; the wavefront
    schedule of ``kernels.stencil2d.stencil_perks_deep``).

    Each pass advances t time steps while reading and writing every
    uncached row exactly ONCE — the inter-block halos ride in VMEM edge
    stashes, so there is no ``2*r*t`` overlap re-read and no per-pass
    resident-edge traffic:

        A_gm = ceil(N/t) * 2*D_uncached + 2*D_cached

    Monotonically non-increasing in t at fixed cache (the planner
    property test pins this), unlike ``gm_bytes_fused`` whose overlap
    term is constant per step. The cost of depth moves entirely into the
    scratch working set (``deep_scratch_rows``), where it competes with
    resident rows for VMEM instead of with HBM bandwidth.
    """
    t = fuse_steps
    passes = -(-n_steps // t)
    uncached = max(0, domain_bytes - cached_bytes)
    return passes * 2.0 * uncached + 2.0 * cached_bytes


def deep_scratch_rows(sub_rows: int, halo: int, fuse_steps: int) -> int:
    """VMEM working-set rows of the deep wavefront kernel beyond the
    resident region: (2t+5) block buffers (triple-buffered level 0, one
    ping-pong pair per inner level, a double-buffered write-back, the
    resident sweep's output) and the stage window (one block plus a halo
    on each side), plus (t+3) halo-row stashes — exactly
    ``kernels.stencil2d._deep_scratch_shapes`` in row units, with ``halo``
    the radius rounded up to the tile rows. Linear in t: this is where
    deep blocking pays for its depth."""
    return ((2 * fuse_steps + 5) * sub_rows + 2 * halo
            + (fuse_steps + 3) * halo)


def cg_arrays(n_rows: int, nnz: int, dtype_bytes: int, index_bytes: int = 4) -> list[CacheableArray]:
    """Cacheable arrays of the PERKS conjugate-gradient solver (§III-B2).

    Per CG iteration (see ``exec/krylov.py``): the residual r is read by the
    dot products and axpy updates (3 loads) and written once; p and x and
    Ap similar; the matrix A is read once and never written. The paper
    singles out r (3 loads + 1 store) > A (1 load); we enumerate all of
    them so the planner can fill remaining budget the way Fig. 9's MIX does.
    """
    vec = n_rows * dtype_bytes
    return [
        CacheableArray("r", vec, 3.0, 1.0),
        CacheableArray("p", vec, 3.0, 1.0),
        CacheableArray("x", vec, 1.0, 1.0),
        CacheableArray("Ap", vec, 2.0, 1.0),
        CacheableArray("A", nnz * (dtype_bytes + index_bytes), 1.0, 0.0),
    ]


def cg_arrays_for(matrix) -> list[CacheableArray]:
    """``cg_arrays`` from a ``repro.sparse`` container (COO/CSR/ELL/SELL).

    Duck-typed on ``shape``/``nnz``/``data.dtype`` so this module stays
    dependency-free. Uses the container's **true** nnz — for padded
    formats the planner must rank A by the bytes it actually streams,
    not the zero-filled slots (a power-law ELL would otherwise look 37x
    its real cost and spuriously evict the vectors).
    """
    return cg_arrays(matrix.shape[0], matrix.nnz, matrix.data.dtype.itemsize)


def bicgstab_arrays(n_rows: int, nnz: int, dtype_bytes: int,
                    index_bytes: int = 4) -> list[CacheableArray]:
    """Cacheable arrays of one BiCGStab iteration (DESIGN.md §10).

    Seven working vectors instead of CG's four, and the matrix streams
    TWICE per iteration (v = A p, then t = A s), which doubles A's traffic
    density relative to CG — on small operators the planner now prefers
    pinning A over the colder vectors (x, rhat), the inverse of the CG
    ranking. Per iteration (see ``kernels.ref.bicgstab_iteration_matvec``):
    r feeds the rho dot, the p update and the s axpy (3 loads, 1 store);
    s feeds t = A s, two stabilization dots and the x/r updates (3/1);
    p is rebuilt and consumed by the SpMV and the x update (3/1); rhat is
    read by two dots and never written; v and t are produced once and
    read twice; x accumulates.
    """
    vec = n_rows * dtype_bytes
    return [
        CacheableArray("r", vec, 3.0, 1.0),
        CacheableArray("s", vec, 3.0, 1.0),
        CacheableArray("p", vec, 3.0, 1.0),
        CacheableArray("v", vec, 2.0, 1.0),
        CacheableArray("t", vec, 2.0, 1.0),
        CacheableArray("rhat", vec, 2.0, 0.0),
        CacheableArray("x", vec, 1.0, 1.0),
        CacheableArray("A", nnz * (dtype_bytes + index_bytes), 2.0, 0.0),
    ]


def bicgstab_arrays_for(matrix) -> list[CacheableArray]:
    """``bicgstab_arrays`` from a ``repro.sparse`` container (true nnz)."""
    return bicgstab_arrays(matrix.shape[0], matrix.nnz,
                           matrix.data.dtype.itemsize)


def gmres_arrays(n_rows: int, m: int, nnz: int, dtype_bytes: int,
                 index_bytes: int = 4) -> list[CacheableArray]:
    """Cacheable arrays of one GMRES(m) cycle, normalized per inner
    Arnoldi step (DESIGN.md §10).

    The headline entry is the basis V — (m+1) vectors that every inner
    step reads twice (the two CGS2 projection passes) and extends once.
    Keeping V on-chip is the PERKS story for GMRES: a cycle that fits
    never round-trips the basis through HBM, which is exactly the traffic
    the restart length m is usually tuned to limit. A streams once per
    inner SpMV; w (the candidate vector) is built, projected twice and
    normalized; x/r only move at cycle boundaries (1/m per inner step,
    rounded to the planner's coarse 1.0 — they are small next to V).
    """
    vec = n_rows * dtype_bytes
    return [
        CacheableArray("V", (m + 1) * vec, 2.0, 1.0),
        CacheableArray("w", vec, 3.0, 1.0),
        CacheableArray("r", vec, 1.0, 1.0),
        CacheableArray("x", vec, 1.0, 1.0),
        CacheableArray("A", nnz * (dtype_bytes + index_bytes), 1.0, 0.0),
    ]


def gmres_arrays_for(matrix, m: int) -> list[CacheableArray]:
    """``gmres_arrays`` from a ``repro.sparse`` container (true nnz)."""
    return gmres_arrays(matrix.shape[0], m, matrix.nnz,
                        matrix.data.dtype.itemsize)
