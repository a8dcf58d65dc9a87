"""Jit'd public wrappers for every Pallas kernel in this package.

On a TPU these dispatch the compiled Mosaic kernels, and nothing falls
back to interpret mode or a jnp reference there: a kernel that Mosaic
cannot compile raises (the gather kernels ``spmv``, ``spmv_sell``, ``cg``,
``bicgstab``, ``gmres_cycle`` — see ``spmv_ell.py``). On any other backend
(the test suite's CPU) they run the same kernel bodies in interpret mode,
which the tests validate against the ``ref.py`` oracles. Model code and
solvers call through these wrappers only.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.common import StencilSpec
from repro.kernels import stencil2d as _s2d
from repro.kernels import spmv_ell as _spmv
from repro.kernels import spmv_sell as _sell
from repro.kernels import cg_fused as _cg
from repro.kernels import krylov_fused as _kry
from repro.kernels import ssm_scan as _ssm
from repro.kernels import decode_attn as _da


@functools.partial(jax.jit, static_argnames=("spec", "steps", "sub_rows"))
def stencil_resident(x, *, spec: StencilSpec, steps: int, sub_rows: int = 128):
    """Small-domain PERKS stencil (whole domain VMEM-resident)."""
    return _s2d.stencil_resident(x, spec, steps=steps, sub_rows=sub_rows)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "steps", "cached_rows", "sub_rows",
                     "fuse_steps"))
def stencil_perks(x, *, spec: StencilSpec, steps: int, cached_rows: int,
                  sub_rows: int = 128, fuse_steps: int = 1):
    """Large-domain PERKS stencil (partial VMEM residency, rest streamed).
    ``fuse_steps=t`` advances t time steps per HBM streaming pass
    (temporal blocking). The kernel updates the domain in place through an
    input/output alias; the wrapper does not donate, so callers keep their
    buffers (XLA inserts the one defensive copy)."""
    return _s2d.stencil_perks(x, spec, steps=steps, cached_rows=cached_rows,
                              sub_rows=sub_rows, fuse_steps=fuse_steps)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "steps", "cached_rows", "sub_rows",
                     "fuse_steps"))
def stencil_perks_deep(x, *, spec: StencilSpec, steps: int, cached_rows: int,
                       sub_rows: int = 128, fuse_steps: int = 1):
    """Deep temporal blocking (wavefront schedule, DESIGN.md §12):
    ``fuse_steps=t`` time steps per HBM streaming pass with every uncached
    row read+written exactly once per pass — no ``radius*t`` redundant
    recompute, so t is no longer capped at ~2–4. Same in-place aliasing
    contract as ``stencil_perks``."""
    return _s2d.stencil_perks_deep(x, spec, steps=steps,
                                   cached_rows=cached_rows,
                                   sub_rows=sub_rows, fuse_steps=fuse_steps)


@functools.partial(jax.jit, static_argnames=("spec", "sub_rows"))
def stencil_baseline_step(x, *, spec: StencilSpec, sub_rows: int = 128):
    """One non-persistent stencil step (host-loop baseline kernel)."""
    return _s2d.stencil_baseline_step(x, spec, sub_rows=sub_rows)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def spmv(data, cols, x, *, block_rows: int = 256):
    """Block-ELL SpMV with the dense vector VMEM-resident."""
    return _spmv.spmv_ell(data, cols, x, block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("c", "k_max"))
def spmv_sell(data, cols, slice_offsets, slice_k, x, *, c: int, k_max: int):
    """SELL-C-σ SpMV (x VMEM-resident; per-slice K via the scalar-
    prefetched offset table). Returns the permuted padded result; gather
    with ``SellMatrix.row_positions()`` to restore row order."""
    return _sell.spmv_sell(data, cols, slice_offsets, slice_k, x,
                           c=c, k_max=k_max)


@functools.partial(jax.jit, static_argnames=("iters", "resident_matrix", "block_rows"))
def cg(data, cols, b, *, iters: int, resident_matrix: bool = True,
       block_rows: int = 256):
    """PERKS conjugate gradient: whole iteration loop in one kernel."""
    return _cg.cg_fused(data, cols, b, iters=iters,
                        resident_matrix=resident_matrix, block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("iters", "resident_matrix", "block_rows"))
def bicgstab(data, cols, b, *, iters: int, resident_matrix: bool = True,
             block_rows: int = 256):
    """PERKS BiCGStab: whole iteration loop in one kernel (two SpMVs per
    iteration; A resident or streamed twice per iteration)."""
    return _kry.bicgstab_fused(data, cols, b, iters=iters,
                               resident_matrix=resident_matrix,
                               block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("m",))
def gmres_cycle(data, cols, x, b, *, m: int):
    """One GMRES(m) restart cycle with the Arnoldi basis VMEM-resident.
    Returns (V, H, beta); the caller owns the small least-squares solve."""
    return _kry.gmres_cycle_fused(data, cols, x, b, m=m)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a, b, c, d, *, chunk: int = 128):
    """Batched Mamba2 SSD scan; batch handled by vmap over the PERKS kernel.
    x (B,T,H,P), dt (B,T,H), a (H,), b/c (B,T,N), d (H,) -> y (B,T,H,P)."""
    f = functools.partial(_ssm.ssm_scan, chunk=chunk)
    return jax.vmap(f, in_axes=(0, 0, None, 0, 0, None))(x, dt, a, b, c, d)


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q, k, v, *, block_s: int = 512):
    """Flash-decode GQA attention against a full KV cache."""
    return _da.decode_attention(q, k, v, block_s=block_s)
