"""PERKS Krylov kernels: BiCGStab's whole loop, GMRES's whole cycle,
inside ONE kernel each.

Same adaptation as ``cg_fused.py`` (paper §V-C generalized): the
iteration state lives in VMEM scratch across iterations, the matrix is
either mapped into VMEM (MIX) or DMA-streamed block-by-block from HBM
(VEC), and one ``pl.pallas_call`` runs the full ``lax.fori_loop``.

* ``bicgstab_fused`` — seven working vectors resident; TWO SpMVs per
  iteration (v = A p, then t = A s), so the streamed variant sweeps A
  twice per iteration — A's traffic density doubles relative to CG,
  which is why ``cache_policy.bicgstab_arrays`` ranks A at 2 loads.
* ``gmres_cycle_fused`` — one restart cycle of GMRES(m): Arnoldi + CGS2
  with the (m+1)-vector basis V pinned in VMEM for the cycle's lifetime
  (V is the output buffer, read/extended in place — the PERKS claim for
  GMRES: the basis never round-trips HBM within a cycle). The small
  (m+1) x m least-squares solve stays on the host (it is O(m^3) scalar
  work; see ``exec.krylov.GMRESProblem.run_resident``).

Oracles: ``ref.bicgstab_run`` / ``ref.gmres_cycle_matvec``.
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


# -- BiCGStab -----------------------------------------------------------------

def _bicgstab_kernel_resident(data_ref, cols_ref, b_ref, x_out, rr_out,
                              r_s, rhat_s, p_s, v_s, *, iters: int):
    """All-resident BiCGStab (vectors in scratch, A mapped into VMEM)."""
    b = b_ref[...]
    x_out[...] = jnp.zeros_like(b)
    r_s[...] = b
    rhat_s[...] = b
    p_s[...] = jnp.zeros_like(b)
    v_s[...] = jnp.zeros_like(b)
    one = jnp.ones((), b.dtype)
    rr0 = jnp.sum(b * b)

    def body(i, carry):
        rho, alpha, omega, rr = carry
        r = r_s[...]
        rhat = rhat_s[...]
        rho_new = jnp.sum(rhat * r)
        beta = _safe_div(rho_new, rho) * _safe_div(alpha, omega)
        p = r + beta * (p_s[...] - omega * v_s[...])
        v = jnp.sum(data_ref[...] * p[cols_ref[...]], axis=1)
        alpha_n = _safe_div(rho_new, jnp.sum(rhat * v))
        s = r - alpha_n * v
        t = jnp.sum(data_ref[...] * s[cols_ref[...]], axis=1)
        omega_n = _safe_div(jnp.sum(t * s), jnp.sum(t * t))
        x_out[...] = x_out[...] + alpha_n * p + omega_n * s
        r = s - omega_n * t
        r_s[...] = r
        p_s[...] = p
        v_s[...] = v
        return rho_new, alpha_n, omega_n, jnp.sum(r * r)

    _, _, _, rr = jax.lax.fori_loop(0, iters, body, (one, one, one, rr0))
    rr_out[...] = rr.reshape(1)


def _bicgstab_kernel_streamed(data_ref, cols_ref, b_ref, x_out, rr_out,
                              r_s, rhat_s, p_s, v_s, mv_s, dbuf, cbuf, sem,
                              *, iters: int, block_rows: int):
    """Vector-resident BiCGStab with A DMA-streamed from HBM — TWICE per
    iteration (v = A p, then t = A s): the VEC regime where A dominates
    traffic at 2x CG's rate."""
    n = b_ref.shape[0]
    bm = block_rows
    nblocks = n // bm

    b = b_ref[...]
    x_out[...] = jnp.zeros_like(b)
    r_s[...] = b
    rhat_s[...] = b
    p_s[...] = jnp.zeros_like(b)
    v_s[...] = jnp.zeros_like(b)
    one = jnp.ones((), b.dtype)
    rr0 = jnp.sum(b * b)

    def _copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def _stream_mv(q):
        for j in range(nblocks):
            _copy(data_ref.at[pl.ds(j * bm, bm)], dbuf)
            _copy(cols_ref.at[pl.ds(j * bm, bm)], cbuf)
            mv_s[pl.ds(j * bm, bm)] = jnp.sum(dbuf[...] * q[cbuf[...]],
                                              axis=1)
        return mv_s[...]

    def body(i, carry):
        rho, alpha, omega, rr = carry
        r = r_s[...]
        rhat = rhat_s[...]
        rho_new = jnp.sum(rhat * r)
        beta = _safe_div(rho_new, rho) * _safe_div(alpha, omega)
        p = r + beta * (p_s[...] - omega * v_s[...])
        v = _stream_mv(p)
        alpha_n = _safe_div(rho_new, jnp.sum(rhat * v))
        s = r - alpha_n * v
        t = _stream_mv(s)
        omega_n = _safe_div(jnp.sum(t * s), jnp.sum(t * t))
        x_out[...] = x_out[...] + alpha_n * p + omega_n * s
        r = s - omega_n * t
        r_s[...] = r
        p_s[...] = p
        v_s[...] = v
        return rho_new, alpha_n, omega_n, jnp.sum(r * r)

    _, _, _, rr = jax.lax.fori_loop(0, iters, body, (one, one, one, rr0))
    rr_out[...] = rr.reshape(1)


def bicgstab_fused(
    data: jax.Array,
    cols: jax.Array,
    b: jax.Array,
    *,
    iters: int,
    resident_matrix: bool = True,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
):
    """Run ``iters`` BiCGStab iterations for A@x=b (A in ELL form) in one
    kernel. Returns (x, rr) with rr = ||r||^2 after the final iteration.
    Oracle: ``repro.kernels.ref.bicgstab_run``."""
    n, k = data.shape
    assert cols.shape == (n, k) and b.shape == (n,)
    interpret = gather_interpret(interpret, "bicgstab_fused")
    out_shape = (
        jax.ShapeDtypeStruct((n,), b.dtype),
        jax.ShapeDtypeStruct((1,), b.dtype),
    )
    if resident_matrix:
        return pl.pallas_call(
            functools.partial(_bicgstab_kernel_resident, iters=iters),
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
            scratch_shapes=[pltpu.VMEM((n,), b.dtype)] * 4,
            interpret=interpret,
        )(data, cols, b)

    bm = min(block_rows, n)
    assert n % bm == 0, "pad n to a multiple of block_rows"
    return pl.pallas_call(
        functools.partial(_bicgstab_kernel_streamed, iters=iters,
                          block_rows=bm),
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
            pltpu.VMEM((n,), b.dtype),      # r
            pltpu.VMEM((n,), b.dtype),      # rhat
            pltpu.VMEM((n,), b.dtype),      # p
            pltpu.VMEM((n,), b.dtype),      # v
            pltpu.VMEM((n,), b.dtype),      # SpMV result buffer
            pltpu.VMEM((bm, k), data.dtype),
            pltpu.VMEM((bm, k), cols.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(data, cols, b)


# -- GMRES(m) cycle -----------------------------------------------------------

def _gmres_cycle_kernel(data_ref, cols_ref, x_ref, b_ref,
                        v_out, h_out, beta_out, *, m: int):
    """One Arnoldi/CGS2 restart cycle with the basis pinned in VMEM.

    V is the output buffer itself: row j+1 is appended in place each
    inner step and both CGS2 projection passes read the whole basis from
    VMEM — zero HBM traffic for V inside the cycle."""
    n = b_ref.shape[0]
    x = x_ref[...]
    b = b_ref[...]
    r = b - jnp.sum(data_ref[...] * x[cols_ref[...]], axis=1)
    beta = jnp.sqrt(jnp.sum(r * r))
    v_out[...] = jnp.zeros((m + 1, n), b.dtype)
    h_out[...] = jnp.zeros((m + 1, m), b.dtype)
    v_out[0, :] = r * _safe_div(1.0, beta)

    def body(j, _):
        V = v_out[...]
        vj = jax.lax.dynamic_slice(V, (j, 0), (1, n))[0]
        w = jnp.sum(data_ref[...] * vj[cols_ref[...]], axis=1)
        h1 = V @ w
        w = w - V.T @ h1
        h2 = V @ w                       # second CGS pass
        w = w - V.T @ h2
        hn = jnp.sqrt(jnp.sum(w * w))
        H = jax.lax.dynamic_update_slice(h_out[...], (h1 + h2)[:, None],
                                         (0, j))
        h_out[...] = jax.lax.dynamic_update_slice(H, hn.reshape(1, 1),
                                                  (j + 1, j))
        v_out[...] = jax.lax.dynamic_update_slice(
            V, (w * _safe_div(1.0, hn))[None], (j + 1, 0))
        return 0

    jax.lax.fori_loop(0, m, body, 0)
    beta_out[...] = beta.reshape(1)


def gmres_cycle_fused(
    data: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    b: jax.Array,
    *,
    m: int,
    interpret: Optional[bool] = None,
):
    """One GMRES(m) restart cycle from iterate ``x`` (A in ELL form), the
    Arnoldi basis VMEM-resident. Returns (V, H, beta) — the caller solves
    the small least-squares problem and updates x on the host (see
    ``exec.krylov.GMRESProblem.run_resident``)."""
    n, k = data.shape
    assert cols.shape == (n, k) and b.shape == (n,) and x.shape == (n,)
    interpret = gather_interpret(interpret, "gmres_cycle_fused")
    out_shape = (
        jax.ShapeDtypeStruct((m + 1, n), b.dtype),
        jax.ShapeDtypeStruct((m + 1, m), b.dtype),
        jax.ShapeDtypeStruct((1,), b.dtype),
    )
    return pl.pallas_call(
        functools.partial(_gmres_cycle_kernel, m=m),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec((n, k), lambda: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, k), lambda: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n,), lambda: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((n,), lambda: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((m + 1, n), lambda: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((m + 1, m), lambda: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,), lambda: (0,), memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(data, cols, x, b)
