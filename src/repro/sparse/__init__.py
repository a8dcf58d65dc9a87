"""repro.sparse — the sparse-matrix data layer for the CG evaluation.

Containers and conversions (``formats``), Matrix Market IO (``io``),
the SuiteSparse-proxy dataset registry (``generate``), and nnz-balanced
row partitioning for distributed CG (``partition``). Host-side numpy
only — the kernels in ``repro.kernels`` consume the flattened arrays.
"""
from repro.sparse.formats import (
    COOMatrix,
    CSRMatrix,
    EllMatrix,
    PaddingReport,
    SellMatrix,
    choose_format,
)
from repro.sparse.generate import (
    DATASETS,
    PROXY_ONCHIP_BYTES,
    REGISTRY,
    DatasetSpec,
    generate,
    irregular_names,
    load_matrix,
    nonsymmetric_names,
    symmetric_names,
)
from repro.sparse.io import read_mtx, read_mtx_csr, write_mtx
from repro.sparse.partition import (
    NnzShards,
    balance_report,
    nnz_balanced_partition,
    partition_nnz,
    shard_by_nnz,
)

__all__ = [
    "COOMatrix",
    "CSRMatrix",
    "EllMatrix",
    "PaddingReport",
    "SellMatrix",
    "choose_format",
    "DATASETS",
    "PROXY_ONCHIP_BYTES",
    "REGISTRY",
    "DatasetSpec",
    "generate",
    "irregular_names",
    "load_matrix",
    "nonsymmetric_names",
    "symmetric_names",
    "read_mtx",
    "read_mtx_csr",
    "write_mtx",
    "NnzShards",
    "balance_report",
    "nnz_balanced_partition",
    "partition_nnz",
    "shard_by_nnz",
]
