"""PERKS stencil kernels: the time loop lives INSIDE the Pallas kernel and
the cached portion of the domain is resident in VMEM across time steps.

This is the paper's central artifact (Fig. 3/4) adapted to TPU:

  GPU                          TPU (here)
  ----------------------------------------------------------------------
  persistent kernel launch     one ``pl.pallas_call`` for all N steps
  time loop + grid.sync()      ``lax.fori_loop`` inside the kernel body
                               (TensorCore grid is sequential -> the loop-
                               carried dependency IS the barrier)
  registers+shared-mem cache   VMEM ``scratch_shapes`` holding the cached
                               rows for the whole kernel lifetime
  uncached domain traffic      explicit HBM<->VMEM DMA per time step
                               (``pltpu.make_async_copy``)

Three entry points (all generic over 2D/3D — blocking is along the leading
axis, ``StencilSpec.apply_rows`` handles the rest):

``resident_step_count`` / ``stencil_resident``
    Small-domain PERKS: the whole domain fits in VMEM; zero HBM traffic
    between time steps (paper Fig. 6 regime).

``stencil_perks``
    Large-domain PERKS: rows [0, cached_rows) stay resident in VMEM for the
    kernel's lifetime; remaining rows are streamed HBM->VMEM->HBM every step
    in leading-axis subtiles (paper Fig. 5 regime, Eq. 5 traffic:
    2*N*D_uncached + 2*D_cached).

``stencil_baseline_step``
    The non-persistent reference: one kernel invocation per time step
    (identical streaming inner loop, steps=1, nothing resident). Used by
    the host-loop baseline so kernel quality is held constant and only the
    execution model differs — the paper's controlled comparison.

``stencil_perks_deep``
    Deep temporal blocking (arXiv:2306.03336, DESIGN.md §12): a wavefront
    schedule over the streamed region in which every HBM pass advances
    ``t ≫ 4`` time steps with NO redundant recompute — each uncached row
    is read and written exactly once per pass, and inter-block halos are
    carried through per-level VMEM edge stashes instead of the ``r*t``-
    wide re-read windows of ``stencil_perks``. The level-0 buffer is
    triple-buffered so the DMA of block i+1 overlaps the compute on
    block i.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cache_policy import deep_scratch_rows
from repro.kernels.common import VMEM_HEADROOM_BYTES, StencilSpec


#: Elements per in-VMEM compute chunk. Mosaic unrolls every vector op
#: over the vregs of its operand, so the chunk bounds both the program
#: size and the kernel's VMEM temporaries (about one MiB of f32).
COMPUTE_CHUNK_ELEMS = 1 << 18

#: Live chunk-sized values per fused step (window, terms, sum, border
#: strips, mask select), in units of one compute window.
_TEMPS_PER_STEP = 6


def row_align(shape, dtype) -> int:
    """Leading-axis rows per memory tile: every VMEM slice and DMA window
    along the leading axis must start and end on this granularity on a
    TPU. A 2D domain's rows are the sublane axis (8 rows of 32-bit, 16 of
    16-bit); the leading axis of a 3D domain is untiled."""
    if len(shape) > 2:
        return 1
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def halo_rows(spec: StencilSpec, fuse_steps: int, align: int) -> int:
    """Halo rows one fused pass reads per side: ``radius*fuse_steps``
    widened to the tile granularity."""
    return -(-spec.radius * fuse_steps // align) * align


def compute_rows(shape, sub_rows: int, align: int) -> int:
    """Rows per in-VMEM compute chunk (``COMPUTE_CHUNK_ELEMS`` worth)."""
    row_elems = math.prod(shape[1:])
    rows = max(align, COMPUTE_CHUNK_ELEMS // row_elems // align * align)
    return min(rows, sub_rows)


#: Bytes per streamed block the planner aims for: large enough to keep
#: each DMA long, small enough to leave VMEM to resident rows (the deep
#: schedule keeps 2t+5 blocks alive, the shallow one three).
BLOCK_BYTES = {"shallow": 4 << 20, "deep": 1 << 20}


def default_sub_rows(shape, dtype, spec: StencilSpec, *, schedule: str,
                     fuse_steps: int = 1) -> int:
    """Streamed block rows for a planned kernel: ``BLOCK_BYTES`` worth,
    tile-aligned, and no fewer than the halo the schedule needs."""
    A = row_align(shape, dtype)
    row_bytes = math.prod(shape[1:]) * jnp.dtype(dtype).itemsize
    rows = max(A, BLOCK_BYTES[schedule] // row_bytes // A * A)
    need = spec.radius * (fuse_steps if schedule == "shallow" else 1)
    return max(rows, -(-need // A) * A)


def _block_geometry(shape, dtype, spec, sub_rows, fuse_steps):
    H = shape[0]
    A = row_align(shape, dtype)
    S = min(sub_rows, H)
    h = halo_rows(spec, fuse_steps, A)
    return A, S, h, compute_rows(shape, S, A)


def _scratch_shapes(shape, dtype, spec, cached_rows, sub_rows, fuse_steps):
    _, S, h, _ = _block_geometry(shape, dtype, spec, sub_rows, fuse_steps)
    rest = tuple(shape[1:])
    one = lambda n: (max(n, 1),) + rest  # zero-size scratch is not allowed
    return [
        pltpu.VMEM(one(cached_rows), dtype),   # dom: resident rows
        pltpu.VMEM(one(h), dtype),             # edge: step-k rows [R, R+h)
        pltpu.VMEM(one(h), dtype),             # carry: rows above a block
        pltpu.VMEM(one(S + 2 * h), dtype),     # sub: block + both halos
        pltpu.VMEM(one(S), dtype),             # wbuf: advanced block
        pltpu.SemaphoreType.DMA,
    ]


def perks_vmem_bytes(shape, dtype, spec, *, cached_rows: int, sub_rows: int,
                     fuse_steps: int) -> int:
    """Whole VMEM footprint of ``stencil_perks`` for this geometry: its
    scratch plus the compute chunk's live temporaries plus headroom. The
    kernel requests exactly this as its scoped-VMEM limit, and the planner
    admits a plan only when it fits the chip's limit."""
    _, S, h, C = _block_geometry(shape, dtype, spec, sub_rows, fuse_steps)
    row_bytes = math.prod(shape[1:]) * jnp.dtype(dtype).itemsize
    scratch_rows = max(cached_rows, 1) + 2 * h + (S + 2 * h) + S
    temp_rows = _TEMPS_PER_STEP * fuse_steps * (C + 2 * h)
    return (scratch_rows + temp_rows) * row_bytes + VMEM_HEADROOM_BYTES


def _aligned(i, A):
    return i if isinstance(i, int) or A == 1 else pl.multiple_of(i, A)


def _copy(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def _advance(spec, src, dst, n, g_base, *, H, ct, h, C, A, masked,
             barrier=False):
    """``dst`` rows [0, n) = ``ct`` steps of ``spec`` applied to ``src``
    rows [0, n + 2h), whose row ``h`` is global row ``g_base``. Runs chunk
    by chunk (``C`` output rows each). With ``masked``, rows inside the
    global Dirichlet border, or outside the domain, keep their old values,
    so rows that ``src`` holds no data for are never read by an updated
    row; without it the caller guarantees the window lies inside the
    domain. All slices of ``src``/``dst`` are tile-aligned when ``h``,
    ``C`` and the offsets are.

    The window keeps its length across the fused steps (its ``radius``
    edge rows go stale, and the valid rows shrink inward). ``barrier``
    (interpret mode only) ends each step at an optimization barrier:
    XLA:CPU contracts multiply-adds into FMAs differently inside one
    fusion than across fusions, and the barrier keeps every step rounding
    exactly as the per-step loop does. Mosaic does not lower
    ``optimization_barrier``, so the compiled kernel has none: the
    bit-exact agreement the CPU tests check is not established on a TPU,
    where results agree with the loop tiers to roundoff."""
    r = spec.radius
    ids = (1,) * (len(src.shape) - 1)

    def chunk(off, rows):
        w = src[pl.ds(_aligned(off, A), rows + 2 * h)]
        L = w.shape[0]
        g = g_base + off - h + r + jax.lax.broadcasted_iota(
            jnp.int32, (L - 2 * r,) + ids, 0)   # global rows of w[r:L-r]
        for s in range(ct):
            upd = spec.apply_rows(w, r, L - r)
            if masked:
                upd = jnp.where((g < r) | (g >= H - r), w[r:L - r], upd)
            if s + 1 < ct:
                w = jnp.concatenate([w[:r], upd, w[L - r:]], axis=0)
                if barrier:
                    w = jax.lax.optimization_barrier(w)
        dst[pl.ds(_aligned(off, A), rows)] = upd[h - r:h - r + rows]

    full, tail = divmod(n, C)
    if full == 1:
        chunk(0, C)
    elif full > 1:
        def body(c, carry):
            chunk(c * C, C)
            return carry
        jax.lax.fori_loop(0, full, body, 0)
    if tail:
        chunk(full * C, tail)


class _Sweep:
    """In-place blocked advance of a row range, shared by both kernels.

    Each block of ``S`` rows is copied into ``sub`` with an ``h``-row halo
    on each side, advanced into ``wbuf`` and copied back in place. The h
    rows above a block were already overwritten at their source, so they
    come from ``carry`` (saved from the previous block's window), from
    ``dom`` (the first streamed block, read before the resident region
    advances) or from nowhere (the domain's top, masked). Rows below the
    resident region come from the ``edge`` stash of their step-k values.
    Every window offset and length is a multiple of the tile rows, so
    every slice and DMA is tile-aligned on a TPU. Blocks that need none of
    the special cases run under one ``fori_loop``; the rest are unrolled.
    """

    def __init__(self, spec, H, R, S, h, C, A, e, *, dom, edge, carry, sub,
                 wbuf, sem, barrier):
        self.spec, self.H, self.R, self.S, self.h = spec, H, R, S, h
        self.C, self.A, self.e, self.barrier = C, A, e, barrier
        self.dom, self.edge, self.carry = dom, edge, carry
        self.sub, self.wbuf, self.sem = sub, wbuf, sem

    def block(self, ref, a, bn, top, n_body, n_edge, save_carry, ct,
              masked):
        """Advance rows [a, a+bn) of ``ref`` in place. ``n_body`` rows from
        row ``a`` come from ``ref``, the next ``n_edge`` from ``edge``."""
        h, A, R, sub = self.h, self.A, self.R, self.sub
        if top == "carry":
            sub[pl.ds(0, h)] = self.carry[...]
        elif top == "dom":
            n = min(h, R)
            sub[pl.ds(h - n, n)] = self.dom[pl.ds(R - n, n)]
        _copy(ref.at[pl.ds(_aligned(a, A), n_body)],
              sub.at[pl.ds(h, n_body)], self.sem)
        if n_edge:
            sub[pl.ds(h + n_body, n_edge)] = self.edge[pl.ds(0, n_edge)]
        if save_carry:
            self.carry[...] = sub[pl.ds(_aligned(bn, A), h)]
        _advance(self.spec, sub, self.wbuf, bn, a, H=self.H, ct=ct, h=h,
                 C=self.C, A=A, masked=masked, barrier=self.barrier)
        _copy(self.wbuf.at[pl.ds(0, bn)], ref.at[pl.ds(_aligned(a, A), bn)],
              self.sem)

    def region(self, ref, lo, hi, first_top, ct):
        """Advance rows [lo, hi) of ``ref`` (the HBM domain, or ``dom``
        with ``hi == R``) by ``ct`` steps."""
        H, R, S, h = self.H, self.R, self.S, self.h
        in_dom = ref is self.dom
        lim = R + self.e if in_dom and hi < H else H
        blocks = []
        for a in range(lo, hi, S):
            bn = min(S, hi - a)
            win_hi = min(a + bn + h, lim)
            n_body = min(win_hi, hi if in_dom else H) - a
            blocks.append(dict(
                a=a, bn=bn, top=first_top if a == lo else "carry",
                n_body=n_body, n_edge=win_hi - a - n_body,
                save_carry=a + bn < hi,
                masked=not (a - h >= 0 and a + bn + h <= H)))
        plain = [b["top"] == "carry" and b["bn"] == S and b["n_edge"] == 0
                 and b["n_body"] == S + h and b["save_carry"]
                 and not b["masked"] for b in blocks]
        i = 0
        while i < len(blocks):
            j = i
            while j < len(blocks) and plain[j]:
                j += 1
            if j - i >= 2:             # a run of uniform blocks: rolled
                a0 = blocks[i]["a"]

                def body(k, carry, a0=a0):
                    self.block(ref, a0 + k * S, S, "carry", S + h, 0, True,
                               ct, False)
                    return carry
                jax.lax.fori_loop(0, j - i, body, 0)
                i = j
            else:
                self.block(ref, ct=ct, **blocks[i])
                i += 1


def _perks_kernel(
    x_ref,         # input ref (aliased to io_ref; unused — all I/O via io_ref)
    io_ref,        # full domain, HBM (ANY), aliased input/output
    dom,           # VMEM scratch: resident rows [0, R)
    edge,          # VMEM scratch: step-k values of rows [R, R+h)
    carry,         # VMEM scratch: step-k values of the h rows above the
                   # current block (already overwritten at their source)
    sub,           # VMEM scratch: block window, h halo rows on each side
    wbuf,          # VMEM scratch: the block advanced by one pass
    sem,           # DMA semaphore
    *,
    spec: StencilSpec,
    steps: int,
    cached_rows: int,
    sub_rows: int,
    fuse_steps: int,
    interpret: bool,
):
    """Each pass advances ``ct`` fused steps over the streamed region, then
    the resident one, block by block (``_Sweep``); block windows carry an
    ``h``-row halo (``radius*fuse_steps`` rounded up to the tile) whose
    inner steps are recomputed redundantly."""
    H = io_ref.shape[0]
    R = cached_rows
    A, S, h, C = _block_geometry(io_ref.shape, io_ref.dtype, spec, sub_rows,
                                 fuse_steps)
    e = min(h, H - R) if 0 < R < H else 0
    sweep = _Sweep(spec, H, R, S, h, C, A, e, dom=dom, edge=edge,
                   carry=carry, sub=sub, wbuf=wbuf, sem=sem,
                   barrier=interpret)

    # Prologue: load the resident region into VMEM once.
    if R > 0:
        _copy(io_ref.at[pl.ds(0, R)], dom, sem)

    def make_pass(ct):
        """One pass advancing ``ct`` fused time steps (the temporal block,
        DESIGN.md §4): every uncached row is read and written once per pass
        instead of once per step."""
        def one_pass(*_):
            # (1) Keep the resident region's bottom halo (rows [R, R+e)) at
            #     step-k values before streaming overwrites it.
            if e > 0:
                _copy(io_ref.at[pl.ds(R, e)], edge.at[pl.ds(0, e)], sem)
            # (2) Streamed blocks, top to bottom, updated in place in HBM.
            if R < H:
                sweep.region(io_ref, R, H, "dom" if R > 0 else "none", ct)
            # (3) Resident region, in place in VMEM: no HBM traffic beyond
            #     the edge stash.
            if R > 0:
                sweep.region(dom, 0, R, "none", ct)
            return ()

        return one_pass

    full, rem = divmod(steps, fuse_steps)
    if full:
        jax.lax.fori_loop(0, full, lambda i, c: make_pass(fuse_steps)(c), ())
    if rem:
        make_pass(rem)(())

    # Epilogue: the resident region's final state goes back to HBM once.
    if R > 0:
        _copy(dom, io_ref.at[pl.ds(0, R)], sem)


def _check_tiling(shape, dtype, cached_rows, sub_rows):
    """Refuse, before Mosaic does, a geometry whose windows cannot be
    tile-aligned on a TPU."""
    A = row_align(shape, dtype)
    bad = {k: v for k, v in (("rows", shape[0]), ("cached_rows", cached_rows),
                             ("sub_rows", min(sub_rows, shape[0])))
           if v % A}
    if bad:
        raise ValueError(
            f"on a TPU every leading-axis window must be a multiple of "
            f"{A} rows for {jnp.dtype(dtype).name}; got {bad}")


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _pallas(kernel, x, scratch, vmem, name, interpret: bool):
    """The one ``pallas_call`` shape both stencil kernels share: the domain
    stays in HBM, aliased in place, and all traffic is explicit DMA."""
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        name=name,
        interpret=interpret,
    )(x)


def _perks_call(x, spec, steps, cached_rows, sub_rows, t, interpret):
    interpret = _interpret(interpret)
    if not interpret:
        _check_tiling(x.shape, x.dtype, cached_rows, sub_rows)
    kernel = functools.partial(
        _perks_kernel, spec=spec, steps=steps, cached_rows=cached_rows,
        sub_rows=sub_rows, fuse_steps=t, interpret=interpret,
    )
    return _pallas(
        kernel, x,
        _scratch_shapes(x.shape, x.dtype, spec, cached_rows, sub_rows, t),
        perks_vmem_bytes(x.shape, x.dtype, spec, cached_rows=cached_rows,
                         sub_rows=sub_rows, fuse_steps=t),
        "stencil_perks", interpret)


def stencil_perks(
    x: jax.Array,
    spec: StencilSpec,
    *,
    steps: int,
    cached_rows: int,
    sub_rows: int = 128,
    fuse_steps: int = 1,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Run ``steps`` time steps of ``spec`` with rows [0, cached_rows)
    VMEM-resident for the kernel's whole lifetime (the PERKS scheme).

    ``cached_rows == x.shape[0]`` gives the fully-resident small-domain
    kernel; ``cached_rows == 0`` streams everything (still persistent:
    one launch for all steps, but no inter-step reuse).

    ``fuse_steps=t`` is temporal blocking (DESIGN.md §4): each HBM
    streaming pass advances t time steps, so the uncached region round-
    trips HBM ceil(steps/t) times instead of ``steps`` times. Block
    windows widen to a ``radius*t`` halo (rounded up to the tile rows) of
    step-k values whose inner steps are recomputed redundantly;
    ``sub_rows`` must cover ``radius*t``.
    """
    H = x.shape[0]
    r = spec.radius
    t = fuse_steps
    assert t >= 1, "fuse_steps must be >= 1"
    assert cached_rows in (0, H) or cached_rows >= r, (
        "partial caching needs at least `radius` resident rows")
    assert cached_rows <= H
    assert sub_rows >= r * min(t, steps), (
        "subtile must cover the next subtile's fused halo "
        f"(sub_rows >= radius*fuse_steps = {r * min(t, steps)})")
    return _perks_call(x, spec, steps, cached_rows, sub_rows, t, interpret)


def _deep_geometry(shape, dtype, spec, cached_rows, sub_rows):
    H = shape[0]
    A = row_align(shape, dtype)
    S = min(sub_rows, max(H - cached_rows, 1))
    h = halo_rows(spec, 1, A)
    if S < h:                   # off-TPU geometries only (_check_tiling)
        h = spec.radius
    return A, S, h, compute_rows(shape, S, A)


def _deep_kernel(
    x_ref,         # input ref (aliased to io_ref; unused — all I/O via io_ref)
    io_ref,        # full domain, HBM (ANY), aliased input/output
    dom,           # VMEM: resident rows [0, R)
    b0,            # VMEM (3, S, ...): level-0 blocks, triple-buffered
    lv,            # VMEM (2(t-1), S, ...): levels 1..t-1, ping-pong pairs
    st,            # VMEM (t, h, ...): per-level edge stashes
    dst,           # VMEM (h, ...): resident region's bottom rows
    wb,            # VMEM (2, S, ...): write-back double buffer
    win,           # VMEM (S+2h, ...): the window a stage advances
    wbuf,          # VMEM (S, ...): resident sweep output
    carry,         # VMEM (h, ...): resident sweep carry
    edge,          # VMEM (h, ...): block 0's top rows, below the resident rows
    si,            # DMA semaphores (3,): inbound, per level-0 slot
    so,            # DMA semaphores (2,): outbound, per write-back slot
    sem,           # DMA semaphore: local copies
    *,
    spec: StencilSpec,
    steps: int,
    cached_rows: int,
    sub_rows: int,
    fuse_steps: int,
):
    """Wavefront deep-temporal-blocking schedule (DESIGN.md §12).

    The streamed region [R, H) is split into m blocks of ``S`` rows. One
    pass runs iterations j = 0..m+t-1; at iteration j, stage k (k = 1..t,
    in increasing order) advances block ``i = j-k`` from time level k-1 to
    level k, so block i finishes all t levels at iteration i+t and is
    written back exactly once. Stage k's window is block i at level k-1
    with an ``h``-row halo on each side (``h`` = radius rounded up to the
    tile rows):

      * block i itself — the level-(k-1) ping-pong slot written last
        iteration by stage k-1 (parity (j-1)%2);
      * the top rows of block i+1 — written THIS iteration by stage k-1
        (parity j%2), which is why stages run in increasing k;
      * the bottom rows of block i-1 — stashed by stage k-1 this iteration
        right before it overwrote that slot (st[k-1]), or read from the
        still-intact slot when stage k-1 was inactive (drain).

    Level 0 is TRIPLE buffered: at iteration j the DMA of block j+1 is
    started before stage 1 computes on block j-1. The resident region
    [0, R) advances one level per iteration (j -> j+1 at the end of
    iteration j < t), coupled to block 0 through ``dst`` and ``edge``, so
    it needs no per-pass HBM traffic at all. Per pass the uncached region
    moves 2*D_uncached bytes regardless of t (``gm_bytes_deep``).

    The iteration loop is one ``fori_loop``: buffer slots are indexed by
    the iteration's parity and the wavefront's ramp-up and drain are
    ``pl.when`` branches. Rows outside the domain (the last block's tail,
    the halo above row 0) hold stale data, and the frozen-row mask keeps
    every row they could reach at its old value.
    """
    H = io_ref.shape[0]
    R = cached_rows
    t = fuse_steps
    A, S, h, C = _deep_geometry(io_ref.shape, io_ref.dtype, spec, R,
                                sub_rows)
    m = -(-(H - R) // S)
    last = (H - R) - (m - 1) * S
    e = min(h, H - R)
    nd = min(h, R)
    sweep = _Sweep(spec, H, R, S, h, C, A, e, dom=dom, edge=edge,
                   carry=carry, sub=win, wbuf=wbuf, sem=sem, barrier=False)

    def sized(i, fn):
        """``fn(n)`` with block i's row count: ``last`` for the final block."""
        if last == S:
            fn(S)
            return
        if isinstance(i, int):
            fn(last if i == m - 1 else S)
            return
        pl.when(i != m - 1)(lambda: fn(S))
        pl.when(i == m - 1)(lambda: fn(last))

    def dma_in(i, n):
        return pltpu.make_async_copy(
            io_ref.at[pl.ds(_aligned(R + i * S, A), n)],
            b0.at[i % 3].at[pl.ds(0, n)], si.at[i % 3])

    def dma_out(i, p, n):
        return pltpu.make_async_copy(
            wb.at[p].at[pl.ds(0, n)],
            io_ref.at[pl.ds(_aligned(R + i * S, A), n)], so.at[p])

    def level(k, p):
        return lv.at[2 * (k - 1) + p]

    def stage(j, k, ct):
        i = j - k
        if k == 1:
            own, below, prev = b0.at[(j - 1) % 3], b0.at[j % 3], \
                b0.at[(j + 1) % 3]
            prev_active = j + 1 < m       # block i-1's slot took a DMA
        else:
            own, below = level(k - 1, (j - 1) % 2), level(k - 1, j % 2)
            prev = below
            prev_active = i + 1 < m       # stage k-1 ran this iteration

        @pl.when((i >= 0) & (i < m))
        def _():
            if R > 0:
                @pl.when(i == 0)
                def _():
                    win[pl.ds(h - nd, nd)] = dst[pl.ds(h - nd, nd)]

            @pl.when((i > 0) & prev_active)
            def _():
                win[pl.ds(0, h)] = st[k - 1]

            @pl.when((i > 0) & jnp.logical_not(prev_active))
            def _():
                win[pl.ds(0, h)] = prev[pl.ds(S - h, h)]

            _copy(own.at[pl.ds(0, S)], win.at[pl.ds(h, S)], sem)
            win[pl.ds(h + S, h)] = below[pl.ds(0, h)]
            if k == ct:
                out = wb.at[j % 2]

                @pl.when(i >= 2)
                def _():
                    dma_out(i - 2, j % 2, S).wait()
            else:
                out = level(k, j % 2)

                # stage k+1 reads block i-2's bottom rows from this slot
                # this iteration: stash them before they are overwritten
                @pl.when(i >= 2)
                def _():
                    st[k] = out[pl.ds(S - h, h)]
            _advance(spec, win, out, S, R + i * S, H=H, ct=1, h=h, C=C,
                     A=A, masked=True)
            if k == ct:
                sized(i, lambda n: dma_out(i, j % 2, n).start())

    def resident_level(j):
        """Advance the resident region from level j to j+1, its bottom
        halo being block 0's top rows at level j."""
        dst[pl.ds(h - nd, nd)] = dom[pl.ds(R - nd, nd)]
        pl.when(j == 0)(lambda: edge.__setitem__(
            pl.ds(0, e), b0.at[0][pl.ds(0, e)]))
        pl.when(j > 0)(lambda: edge.__setitem__(
            pl.ds(0, e), lv.at[2 * (j - 1) + j % 2][pl.ds(0, e)]))
        sweep.region(dom, 0, R, "none", 1)

    # Prologue: load the resident region into VMEM once.
    if R > 0:
        _copy(io_ref.at[pl.ds(0, R)], dom, sem)

    def make_wave(ct):
        """One wavefront pass advancing ``ct`` time steps."""

        def iteration(j, c):
            # The DMA below overwrites slot (j+1)%3, which still holds
            # block j-2 — stash its bottom rows if stage 1 reads them this
            # iteration (its block-(j-1) above-halo).
            @pl.when((j + 1 < m) & (j >= 2))
            def _():
                st[0] = b0.at[(j + 1) % 3][pl.ds(S - h, h)]

            pl.when(j + 1 < m)(lambda: sized(
                j + 1, lambda n: dma_in(j + 1, n).start()))
            pl.when(j < m)(lambda: sized(j, lambda n: dma_in(j, n).wait()))
            for k in range(1, ct + 1):
                stage(j, k, ct)
            if R > 0:
                pl.when(j < ct)(lambda: resident_level(j))
            return c

        def wave(*_):
            sized(0, lambda n: dma_in(0, n).start())
            jax.lax.fori_loop(0, m + ct, iteration, 0)
            for i in range(max(m - 2, 0), m):   # the last two write-backs
                sized(i, lambda n: dma_out(i, (i + ct) % 2, n).wait())
            return ()

        return wave

    full, rem = divmod(steps, t)
    if full:
        jax.lax.fori_loop(0, full, lambda i, c: make_wave(t)(c), ())
    if rem:
        make_wave(rem)(())

    # Epilogue: the resident region's final state goes back to HBM once.
    if R > 0:
        _copy(dom, io_ref.at[pl.ds(0, R)], sem)


def _deep_scratch_shapes(shape, dtype, spec, cached_rows, sub_rows,
                         fuse_steps):
    _, S, h, _ = _deep_geometry(shape, dtype, spec, cached_rows, sub_rows)
    t = fuse_steps
    rest = tuple(shape[1:])
    one = lambda n: (max(n, 1),) + rest  # zero-size scratch is not allowed
    return [
        pltpu.VMEM(one(cached_rows), dtype),              # dom
        pltpu.VMEM((3,) + one(S), dtype),                 # b0
        pltpu.VMEM((max(2 * (t - 1), 1),) + one(S), dtype),  # lv
        pltpu.VMEM((t,) + one(h), dtype),                 # st
        pltpu.VMEM(one(h), dtype),                        # dst
        pltpu.VMEM((2,) + one(S), dtype),                 # wb
        pltpu.VMEM(one(S + 2 * h), dtype),                # win
        pltpu.VMEM(one(S), dtype),                        # wbuf
        pltpu.VMEM(one(h), dtype),                        # carry
        pltpu.VMEM(one(h), dtype),                        # edge
        pltpu.SemaphoreType.DMA((3,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA,
    ]


def deep_vmem_bytes(shape, dtype, spec, *, cached_rows: int, sub_rows: int,
                    fuse_steps: int) -> int:
    """Whole VMEM footprint of ``stencil_perks_deep`` (scratch, one
    stage's live temporaries, headroom): its scoped-VMEM request, and the
    figure the planner admits a deep plan by."""
    if cached_rows >= shape[0]:
        return perks_vmem_bytes(shape, dtype, spec, cached_rows=cached_rows,
                                sub_rows=sub_rows, fuse_steps=fuse_steps)
    _, S, h, C = _deep_geometry(shape, dtype, spec, cached_rows, sub_rows)
    row_bytes = math.prod(shape[1:]) * jnp.dtype(dtype).itemsize
    rows = (max(cached_rows, 1) + deep_scratch_rows(S, h, fuse_steps)
            + _TEMPS_PER_STEP * (C + 2 * h))
    return rows * row_bytes + VMEM_HEADROOM_BYTES


def stencil_perks_deep(
    x: jax.Array,
    spec: StencilSpec,
    *,
    steps: int,
    cached_rows: int,
    sub_rows: int = 128,
    fuse_steps: int = 1,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Deep temporal blocking: ``fuse_steps=t`` time steps per HBM pass
    with NO redundant recompute (arXiv:2306.03336; DESIGN.md §12).

    Where ``stencil_perks`` widens every block window to an ``r*t`` halo
    of step-k values and redundantly recomputes the inner steps (cost
    linear in t, useful depth ~2–4), the wavefront schedule keeps ``t``
    time levels of block edges alive in VMEM ping-pong buffers so each
    streamed row is read once and written once per pass at ANY depth:

        A_gm = ceil(N/t) * 2*D_uncached + 2*D_cached

    (``core.cache_policy.gm_bytes_deep``) — monotonically non-increasing
    in t, vs. the shallow kernel's per-pass ``2*r*t`` overlap re-read.
    The price is scratch: ``deep_scratch_rows`` grows linearly in t, so
    depth trades against resident rows under the planner's VMEM budget.
    A fully resident domain has nothing to stream and runs the shallow
    kernel's in-VMEM sweep.

    Validity needs only ``sub_rows >= radius`` (one level's halo), NOT
    the shallow ``radius*fuse_steps`` bound — that is what unlocks
    t >> 4. Bit-equivalence vs the loop tiers holds to the same <= 2-ulp
    reassociation bound as the shallow kernel (tests/test_deep_blocking).
    """
    H = x.shape[0]
    r = spec.radius
    t = max(1, min(fuse_steps, steps)) if steps else 1
    assert fuse_steps >= 1, "fuse_steps must be >= 1"
    assert cached_rows in (0, H) or cached_rows >= r, (
        "partial caching needs at least `radius` resident rows")
    assert cached_rows <= H
    assert sub_rows >= r, (
        "deep schedule needs one level's halo per block "
        f"(sub_rows >= radius = {r}, got {sub_rows})")
    if cached_rows >= H:
        return _perks_call(x, spec, steps, H, sub_rows, t, interpret)
    interpret = _interpret(interpret)
    if not interpret:
        _check_tiling(x.shape, x.dtype, cached_rows, sub_rows)
    kernel = functools.partial(
        _deep_kernel, spec=spec, steps=steps,
        cached_rows=cached_rows, sub_rows=sub_rows, fuse_steps=t,
    )
    return _pallas(
        kernel, x,
        _deep_scratch_shapes(x.shape, x.dtype, spec, cached_rows, sub_rows,
                             t),
        deep_vmem_bytes(x.shape, x.dtype, spec, cached_rows=cached_rows,
                        sub_rows=sub_rows, fuse_steps=t),
        "stencil_perks_deep", interpret)


def stencil_resident(
    x: jax.Array,
    spec: StencilSpec,
    *,
    steps: int,
    sub_rows: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Small-domain PERKS: the whole domain lives in VMEM for all steps.

    HBM traffic is exactly one domain load + one domain store total,
    independent of ``steps`` (Eq. 5 with D_uncached = 0). This is
    ``stencil_perks`` with every row cached.
    """
    return stencil_perks(x, spec, steps=steps, cached_rows=x.shape[0],
                         sub_rows=sub_rows, interpret=interpret)


def stencil_baseline_step(
    x: jax.Array,
    spec: StencilSpec,
    *,
    sub_rows: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One non-persistent time step (the host-loop baseline's kernel):
    identical streaming machinery, nothing survives the call."""
    return stencil_perks(x, spec, steps=1, cached_rows=0,
                         sub_rows=sub_rows, interpret=interpret)
