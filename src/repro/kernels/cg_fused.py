"""PERKS Conjugate Gradient: the whole CG iteration loop inside ONE kernel.

The paper's CG experiment (§V-C, Fig. 7/9): move the time loop of the CG
solver into a persistent kernel and keep the iteration state — the vectors
x, r, p (and the SpMV result Ap) — cached on chip across iterations; the
matrix A is streamed (or cached too, when it fits: Fig. 9's MAT/MIX
policies). Per §III-B2 the vectors outrank the matrix (r: 3 loads + 1 store
per element per iteration; A: 1 load), so vectors are *always* resident.

TPU adaptation: one ``pl.pallas_call`` runs ``iters`` textbook CG
iterations via ``lax.fori_loop``; x/r/p/Ap live in VMEM ``scratch_shapes``
for the kernel's lifetime. Two matrix policies:

  * ``resident_matrix=True``  — A's ELL blocks are mapped into VMEM by the
    BlockSpec and read from there every iteration (Fig. 9 "MIX": vectors +
    matrix cached). Zero HBM traffic inside the loop.
  * ``resident_matrix=False`` — A stays in HBM (``pl.ANY``) and is DMA-
    streamed block-by-block every iteration (Fig. 9 "VEC": only vectors
    cached; A traffic = iters * nnz, exactly the paper's Eq. 5 uncached
    term).

The dot products (rr, p.Ap) are the device-wide barrier of the paper: every
iteration's scalars depend on the whole domain, which on a mesh becomes a
psum (see solvers/cg.py for the distributed wrapper).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmv_ell import gather_interpret


def _safe_div(a, b):
    return jnp.where(jnp.abs(b) > 0, a / jnp.where(b == 0, 1.0, b), 0.0)


def _cg_kernel_resident(data_ref, cols_ref, b_ref, x_out, rr_out,
                        r_s, p_s, *, iters: int):
    """All-resident CG (vectors in scratch, A mapped into VMEM)."""
    b = b_ref[...]
    x_out[...] = jnp.zeros_like(b)
    r_s[...] = b
    p_s[...] = b
    rr0 = jnp.sum(b * b)

    def body(i, rr):
        p = p_s[...]
        ap = jnp.sum(data_ref[...] * p[cols_ref[...]], axis=1)
        alpha = _safe_div(rr, jnp.sum(p * ap))
        x_out[...] = x_out[...] + alpha * p
        r = r_s[...] - alpha * ap
        r_s[...] = r
        rr_new = jnp.sum(r * r)
        p_s[...] = r + _safe_div(rr_new, rr) * p
        return rr_new

    rr = jax.lax.fori_loop(0, iters, body, rr0)
    rr_out[...] = rr.reshape(1)


def _cg_kernel_streamed(data_ref, cols_ref, b_ref, x_out, rr_out,
                        r_s, p_s, ap_s, dbuf, cbuf, sem,
                        *, iters: int, block_rows: int):
    """Vector-resident CG with the matrix DMA-streamed from HBM each
    iteration (the large-problem regime of Fig. 7, right half)."""
    n = b_ref.shape[0]
    bm = block_rows
    nblocks = n // bm

    b = b_ref[...]
    x_out[...] = jnp.zeros_like(b)
    r_s[...] = b
    p_s[...] = b
    rr0 = jnp.sum(b * b)

    def _copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def body(i, rr):
        p = p_s[...]
        for j in range(nblocks):
            _copy(data_ref.at[pl.ds(j * bm, bm)], dbuf)
            _copy(cols_ref.at[pl.ds(j * bm, bm)], cbuf)
            ap_s[pl.ds(j * bm, bm)] = jnp.sum(dbuf[...] * p[cbuf[...]], axis=1)
        ap = ap_s[...]
        alpha = _safe_div(rr, jnp.sum(p * ap))
        x_out[...] = x_out[...] + alpha * p
        r = r_s[...] - alpha * ap
        r_s[...] = r
        rr_new = jnp.sum(r * r)
        p_s[...] = r + _safe_div(rr_new, rr) * p
        return rr_new

    rr = jax.lax.fori_loop(0, iters, body, rr0)
    rr_out[...] = rr.reshape(1)


def cg_fused(
    data: jax.Array,
    cols: jax.Array,
    b: jax.Array,
    *,
    iters: int,
    resident_matrix: bool = True,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
):
    """Run ``iters`` CG iterations for A@x=b (A in ELL form) in one kernel.

    Returns (x, rr) with rr = ||r||^2 after the final iteration. Oracle:
    ``repro.kernels.ref.cg_run``.
    """
    n, k = data.shape
    assert cols.shape == (n, k) and b.shape == (n,)
    interpret = gather_interpret(interpret, "cg_fused")
    out_shape = (
        jax.ShapeDtypeStruct((n,), b.dtype),
        jax.ShapeDtypeStruct((1,), b.dtype),
    )
    if resident_matrix:
        return pl.pallas_call(
            functools.partial(_cg_kernel_resident, iters=iters),
            out_shape=out_shape,
            in_specs=[
                pl.BlockSpec((n, k), lambda: (0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((n, k), lambda: (0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((n,), lambda: (0,), memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((n,), lambda: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((1,), lambda: (0,), memory_space=pltpu.VMEM),
            ),
            scratch_shapes=[pltpu.VMEM((n,), b.dtype)] * 2,
            interpret=interpret,
        )(data, cols, b)

    bm = min(block_rows, n)
    assert n % bm == 0, "pad n to a multiple of block_rows"
    return pl.pallas_call(
        functools.partial(_cg_kernel_streamed, iters=iters, block_rows=bm),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((n,), lambda: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((n,), lambda: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((1,), lambda: (0,), memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((n,), b.dtype),
            pltpu.VMEM((n,), b.dtype),
            pltpu.VMEM((n,), b.dtype),
            pltpu.VMEM((bm, k), data.dtype),
            pltpu.VMEM((bm, k), cols.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(data, cols, b)
