"""Block-ELL SpMV: the TPU-native replacement for merge-based CSR SpMV.

The paper's CG solver uses Merrill & Garland's merge-based SpMV, which load-
balances CSR by giving every CUDA thread an equal share of the (row_ptr,
nnz) merge path via per-thread binary search. That mechanism is built on
per-lane divergent control flow — it has no analogue on a TPU's vector/
systolic datapath (DESIGN.md §2). The TPU-idiomatic equivalent:

  * pad each row to a fixed ``K`` slots (ELL format) — static shapes do the
    load-balancing that merge-path did dynamically;
  * tile rows into blocks of ``bm``; stream ``(bm, K)`` coefficient/index
    blocks HBM->VMEM;
  * keep the **dense vector x resident in VMEM** across all row blocks —
    this is the PERKS caching decision (vector > matrix, paper §III-B2):
    x is read K times per row (gather) while A is read once.

The gather ``x[cols]`` does NOT lower on a TPU: Mosaic compiles only
gathers that stay inside one vreg ("Only 2D gather is supported" for
``x[cols]``; ``take_along_axis`` over a vector wider than 128 lanes is
refused too). So this kernel and every kernel that gathers ``x`` by
column index (``spmv_sell``, ``cg_fused``, ``krylov_fused``) run only in
interpret mode, off the TPU: ``gather_interpret`` refuses a Mosaic
compile, and the planner offers no resident Krylov plan for a chip that
compiles its kernels (``Chip.interpret`` unset). The loop tiers' SpMV is
the XLA gather of ``ref.spmv_ell``, which the TPU runs. The oracle in
``ref.py`` is identical math.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def gather_interpret(interpret: Optional[bool], kernel: str) -> bool:
    """Resolve a gather kernel's ``interpret`` flag (by default: off the
    TPU), refusing a Mosaic compile (``interpret=False``) with the
    reason."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret:
        raise NotImplementedError(
            f"{kernel} gathers x by column index, which Mosaic cannot "
            "compile for a TPU (only gathers within one vreg lower); on a "
            "TPU plan a loop or distributed tier, whose SpMV is XLA's "
            "gather")
    return interpret


def _spmv_kernel(data_ref, cols_ref, x_ref, y_ref):
    """One row block: y[block] = sum_k data[:, k] * x[cols[:, k]]."""
    x = x_ref[...]
    gathered = x[cols_ref[...]]          # (bm, K) gather from resident x
    y_ref[...] = jnp.sum(data_ref[...] * gathered, axis=1)


def spmv_ell(
    data: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    *,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y = A @ x, A in ELL format: data/cols (n_rows, K), x (n,).

    Rows are streamed in blocks; x stays VMEM-resident for the whole call
    (every grid step maps the full x into VMEM — Pallas keeps it there
    because the block index is constant).
    """
    n_rows, k = data.shape
    assert cols.shape == (n_rows, k)
    interpret = gather_interpret(interpret, "spmv_ell")
    bm = min(block_rows, n_rows)
    # auto-pad the row dimension to a block multiple (zero rows: data 0,
    # col 0 -> y 0) and slice the result back, so arbitrary sizes work
    n_pad = -(-n_rows // bm) * bm
    if n_pad != n_rows:
        data = jnp.concatenate(
            [data, jnp.zeros((n_pad - n_rows, k), data.dtype)])
        cols = jnp.concatenate(
            [cols, jnp.zeros((n_pad - n_rows, k), cols.dtype)])
    grid = (n_pad // bm,)
    y = pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        out_shape=jax.ShapeDtypeStruct((n_pad,), x.dtype),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((x.shape[0],), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm,), lambda i: (i,), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(data, cols, x)
    return y if n_pad == n_rows else y[:n_rows]


# -- host-side ELL construction helpers (numpy; data-prep, not hot path) ----

def dense_to_ell(a: np.ndarray, k: Optional[int] = None):
    """Convert a dense matrix to ELL (data, cols) with per-row padding.

    An explicit ``k`` smaller than some row's nnz raises (naming the
    offending row) — silently dropping entries would corrupt the
    operator.
    """
    n = a.shape[0]
    nnz_per_row = (a != 0).sum(axis=1)
    if k is None:
        k = int(nnz_per_row.max()) if n else 1
    elif n and nnz_per_row.max() > k:
        bad = int(np.argmax(nnz_per_row > k))
        raise ValueError(
            f"ELL k={k} cannot hold row {bad} with {int(nnz_per_row[bad])} "
            f"nonzeros (max row nnz is {int(nnz_per_row.max())})")
    data = np.zeros((n, k), a.dtype)
    cols = np.zeros((n, k), np.int32)
    for i in range(n):
        idx = np.nonzero(a[i])[0]
        data[i, : len(idx)] = a[i, idx]
        cols[i, : len(idx)] = idx
    return data, cols


def poisson2d_ell(side: int, dtype=np.float32):
    """ELL form of the 2D 5-point Poisson matrix on a side x side grid —
    the canonical SPD test operator (the paper's CG datasets are SPD).
    Slot 0 holds the diagonal; the present neighbours follow in the order
    up, down, left, right; absent ones leave zero slots at the end."""
    n = side * side
    rows = np.arange(n)
    r, c = np.divmod(rows, side)
    data = np.zeros((n, 5), dtype)
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
