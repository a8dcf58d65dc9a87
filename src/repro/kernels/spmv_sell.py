"""SELL-C-σ SpMV: load-balanced sparse matvec for irregular matrices.

``spmv_ell.py`` pads every row to the *global* max nnz — the TPU-static
stand-in for merge-based CSR load balancing — which explodes on
irregular matrices (a power-law hub row pads the whole operator to its
degree; see ``repro.sparse.formats``). SELL-C-σ (Kreutzer et al. 2014)
keeps the static shapes but pads each C-row slice only to its own width
``K_s``, after sorting rows by nnz within σ-sized windows.

Kernel mapping:

  * grid = one step per slice; the slice offset/width tables ride in as
    **scalar-prefetched** SMEM operands (``PrefetchScalarGridSpec``) so
    the DMA of each slice can be issued from a dynamic flat offset;
  * the flat ``data``/``cols`` streams stay in HBM (``pl.ANY``) and each
    slice DMAs a fixed ``C * K_max`` window into VMEM scratch — static
    shape, dynamic start. For slices narrower than ``K_max`` the window
    tail overlaps the next slice and is masked off (``slot >= K_s``);
    the wrapper pads the streams by one window so the last slice's read
    stays in bounds;
  * the dense vector x is mapped whole into VMEM with a constant block
    index — **VMEM-resident across all slices**, the paper's §III-B2
    caching decision (x is gathered K times per row, A is read once);
  * slices are stored slot-major (element (r, j) at ``off + j*C + r``),
    so the window reshapes directly to (K_max, C) slot-rows.

Output is in *permuted, padded* row order (n_slices * C rows) — this
holds for ``ops.spmv_sell`` too. Callers restore original order with a
``SellMatrix.row_positions()`` gather; ``solvers.cg.SellOperator.matvec``
is the wrapper that does both steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmv_ell import gather_interpret


def _sell_kernel(off_ref, k_ref, data_ref, cols_ref, x_ref, y_ref,
                 dbuf, cbuf, sem, *, c: int, k_max: int):
    """One slice: y[slice] = sum_j data[j*C:r] * x[cols[j*C:r]], j < K_s."""
    s = pl.program_id(0)
    off = off_ref[s]
    # independent window copies: start both, then wait, so the two
    # HBM->VMEM latencies overlap
    copies = [
        pltpu.make_async_copy(src.at[pl.ds(off, c * k_max)], dst, sem.at[i])
        for i, (src, dst) in enumerate(((data_ref, dbuf), (cols_ref, cbuf)))
    ]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    d = dbuf[...].reshape(k_max, c)        # slot-major: window row j = slot j
    cols = cbuf[...].reshape(k_max, c)
    live = jax.lax.broadcasted_iota(jnp.int32, (k_max, c), 0) < k_ref[s]
    x = x_ref[...]
    y_ref[...] = jnp.sum(jnp.where(live, d * x[cols], 0.0), axis=0)


def spmv_sell(
    data: jax.Array,
    cols: jax.Array,
    slice_offsets: jax.Array,
    slice_k: jax.Array,
    x: jax.Array,
    *,
    c: int,
    k_max: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y_perm = A_perm @ x for A in SELL-C-σ layout.

    data/cols: flat slot-major streams (see ``repro.sparse.SellMatrix``);
    slice_offsets/slice_k: (n_slices,) int32 tables; x: (n_cols,) dense.
    Returns the (n_slices * c,) result in permuted padded row order.
    ``c``/``k_max`` must be static (they size the VMEM scratch window).
    """
    n_slices = slice_offsets.shape[0]
    assert slice_k.shape == (n_slices,)
    interpret = gather_interpret(interpret, "spmv_sell")
    # one extra window of zeros keeps the last slice's fixed-size read
    # in bounds (its tail is masked anyway)
    data = jnp.concatenate([data, jnp.zeros(c * k_max, data.dtype)])
    cols = jnp.concatenate([cols, jnp.zeros(c * k_max, cols.dtype)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slices,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((x.shape[0],), lambda s, *_: (0,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((c,), lambda s, *_: (s,),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((c * k_max,), data.dtype),
            pltpu.VMEM((c * k_max,), cols.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_sell_kernel, c=c, k_max=k_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slices * c,), x.dtype),
        interpret=interpret,
    )(slice_offsets, slice_k, data, cols, x)
