"""nnz-balanced contiguous row partitioning for distributed SpMV/CG.

Distributed CG (``exec.adapters.cg_distributed``) row-partitions the
matrix over a mesh axis. Equal-*rows* sharding balances vector work but not SpMV work: on a
power-law graph one shard can own most of the nonzeros and every psum
barrier waits for it. Equal-*nnz* contiguous ranges are the standard fix
(the same objective merge-based CSR pursues per-thread, applied at the
shard level where a TPU can afford it — once, on the host, at data-prep
time).

``shard_map`` needs equal-shaped shards, so ``shard_by_nnz`` pads every
range to the longest one's row count with explicit zero rows (data 0 /
col 0 / rhs 0): padded rows produce Ap = 0, contribute 0 to every dot
product, and keep x at 0 — algebraically invisible to CG. Column indices
are remapped into the padded row order so the gather against the
replicated search direction stays local-index-free.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def nnz_balanced_partition(row_nnz: np.ndarray, parts: int) -> np.ndarray:
    """Contiguous row ranges with near-equal nnz.

    Returns ``bounds`` of shape (parts + 1,), ``bounds[0] = 0`` and
    ``bounds[-1] = n``; part j owns rows [bounds[j], bounds[j+1]).
    Greedy prefix targets: bound j is placed where the nnz prefix first
    reaches j/parts of the total, which guarantees

        max_part_nnz <= total/parts + max_row_nnz

    (each part overshoots its ideal share by at most the row that
    crossed the target). Empty parts are possible only when there are
    fewer nonzero rows than parts.
    """
    row_nnz = np.asarray(row_nnz, np.int64)
    n = row_nnz.shape[0]
    if parts < 1:
        raise ValueError(f"parts must be >= 1, got {parts}")
    if parts > n:
        raise ValueError(f"cannot split {n} rows into {parts} parts")
    prefix = np.concatenate([[0], np.cumsum(row_nnz)])
    total = prefix[-1]
    targets = total * np.arange(1, parts, dtype=np.float64) / parts
    cuts = np.searchsorted(prefix, targets, side="left")
    bounds = np.concatenate([[0], cuts, [n]])
    return np.maximum.accumulate(np.minimum(bounds, n))


def partition_nnz(bounds: np.ndarray, row_nnz: np.ndarray) -> np.ndarray:
    """nnz owned by each part under ``bounds``."""
    prefix = np.concatenate([[0], np.cumsum(np.asarray(row_nnz, np.int64))])
    return np.diff(prefix[bounds])


def balance_report(bounds: np.ndarray, row_nnz: np.ndarray) -> dict:
    """Imbalance metrics: max/mean part nnz (1.0 = perfectly balanced)."""
    per = partition_nnz(bounds, row_nnz)
    mean = per.mean() if len(per) else 0.0
    rows = np.diff(bounds)
    return {
        "parts": len(per),
        "nnz_per_part": per,
        "rows_per_part": rows,
        "imbalance": float(per.max() / mean) if mean else 1.0,
        "max_rows": int(rows.max()) if len(rows) else 0,
    }


@dataclasses.dataclass(frozen=True)
class NnzShards:
    """Equal-shaped, nnz-balanced ELL shards ready for ``shard_map``.

    ``data``/``cols`` are (parts * rows_per_part, k) with column indices
    remapped to padded row order; ``b`` the reordered/padded rhs;
    ``pos[i]`` the padded position of original row i (the gather that
    restores original ordering on any per-row result).
    """

    data: np.ndarray
    cols: np.ndarray
    b: np.ndarray
    pos: np.ndarray
    bounds: np.ndarray
    rows_per_part: int


def shard_by_nnz(data: np.ndarray, cols: np.ndarray, b: np.ndarray,
                 parts: int) -> NnzShards:
    """Repack an ELL matrix + rhs into nnz-balanced equal-shaped shards.

    Row nnz is taken from the ELL padding (slots with data == 0 count as
    padding — exact for matrices built by ``CSRMatrix.to_ell``, whose
    stored entries are true nonzeros).
    """
    data = np.asarray(data)
    cols = np.asarray(cols)
    b = np.asarray(b)
    n, k = data.shape
    row_nnz = (data != 0).sum(axis=1)
    bounds = nnz_balanced_partition(row_nnz, parts)
    rows_per = int(np.diff(bounds).max())
    n_pad = parts * rows_per
    # padded position of each original row: part-local offset + part base
    part_of = np.repeat(np.arange(parts), np.diff(bounds))
    local = np.arange(n) - bounds[part_of]
    pos = part_of * rows_per + local
    data_p = np.zeros((n_pad, k), data.dtype)
    cols_p = np.zeros((n_pad, k), cols.dtype)
    b_p = np.zeros(n_pad, b.dtype)
    data_p[pos] = data
    # remap column ids into padded order; ELL padding slots point at
    # column 0 -> pos[0], harmless because their data is 0
    cols_p[pos] = pos[cols].astype(cols.dtype)
    b_p[pos] = b
    return NnzShards(data_p, cols_p, b_p, pos, bounds, rows_per)
