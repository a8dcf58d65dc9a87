"""repro.sparse: format round-trips, .mtx IO, SELL-C-σ kernel oracle
equivalence over the full SuiteSparse-proxy registry, and nnz-balanced
partition bounds."""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_policy import cg_arrays, cg_arrays_for
from repro.exec import CGProblem, Plan, execute
from repro.exec.planner import cg_policy_from_arrays
from repro.kernels import ops, ref
from repro.kernels.spmv_ell import dense_to_ell
from repro.sparse import (
    COOMatrix,
    CSRMatrix,
    PROXY_ONCHIP_BYTES,
    REGISTRY,
    balance_report,
    choose_format,
    generate,
    irregular_names,
    nnz_balanced_partition,
    nonsymmetric_names,
    partition_nnz,
    read_mtx,
    read_mtx_csr,
    shard_by_nnz,
    symmetric_names,
    write_mtx,
)
from repro.sparse.generate import skew_shifted_random

KEY = jax.random.key(7)


def _random_sparse(rng, n, m, density=0.15, dtype=np.float32):
    a = rng.standard_normal((n, m)).astype(dtype)
    a[rng.random((n, m)) > density] = 0.0
    return a


# -- container round trips ----------------------------------------------------

@pytest.mark.parametrize("n,m", [(37, 41), (64, 64), (1, 9), (33, 5)])
def test_dense_coo_csr_roundtrip(rng, n, m):
    a = _random_sparse(rng, n, m)
    coo = COOMatrix.from_dense(a)
    csr = coo.to_csr()
    np.testing.assert_array_equal(coo.to_dense(), a)
    np.testing.assert_array_equal(csr.to_dense(), a)
    np.testing.assert_array_equal(csr.to_coo().to_csr().to_dense(), a)


def test_coo_duplicates_sum(rng):
    coo = COOMatrix(np.array([0, 0, 2]), np.array([1, 1, 0]),
                    np.array([2.0, 3.0, 4.0], np.float32), (3, 3))
    d = coo.to_csr().to_dense()
    assert d[0, 1] == 5.0 and d[2, 0] == 4.0 and coo.to_csr().nnz == 2


@pytest.mark.parametrize("c,sigma", [(4, 4), (4, 16), (8, 64), (8, 1024)])
def test_csr_ell_sell_roundtrip(rng, c, sigma):
    a = _random_sparse(rng, 37, 41)       # n not a multiple of c on purpose
    csr = CSRMatrix.from_dense(a)
    np.testing.assert_array_equal(csr.to_ell().to_dense(), a)
    sell = csr.to_sell(c=c, sigma=sigma)
    np.testing.assert_array_equal(sell.to_dense(), a)
    # SELL never stores more slots than ELL (per-slice K <= global K)
    assert sell.stored <= csr.to_ell().data.size
    assert sell.nnz == csr.nnz


def test_empty_rows_roundtrip():
    a = np.zeros((12, 12), np.float32)
    a[3, 4] = 2.0
    csr = CSRMatrix.from_dense(a)
    np.testing.assert_array_equal(csr.to_ell().to_dense(), a)
    np.testing.assert_array_equal(csr.to_sell(c=8, sigma=8).to_dense(), a)


def test_to_ell_explicit_k_raises():
    a = np.eye(4, dtype=np.float32)
    a[2] = 1.0                            # row 2 has 4 nonzeros
    with pytest.raises(ValueError, match="row 2"):
        CSRMatrix.from_dense(a).to_ell(k=2)
    # satellite: dense_to_ell must raise too, not truncate silently
    with pytest.raises(ValueError, match="row 2"):
        dense_to_ell(a, k=2)
    data, cols = dense_to_ell(a, k=6)     # roomy k still fine
    assert data.shape == (4, 6)


def test_spmv_ell_autopads_row_dim(rng):
    """satellite: n_rows need not divide block_rows any more."""
    a = _random_sparse(rng, 100, 100)
    data, cols = dense_to_ell(a)
    x = rng.standard_normal(100).astype(np.float32)
    got = ops.spmv(jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x),
                   block_rows=32)
    assert got.shape == (100,)
    np.testing.assert_allclose(got, a @ x, atol=1e-4)


# -- matrix market IO ---------------------------------------------------------

def test_mtx_roundtrip_general(rng):
    a = _random_sparse(rng, 23, 17)
    buf = io.StringIO()
    write_mtx(buf, CSRMatrix.from_dense(a), comment="proxy test matrix")
    text = buf.getvalue()
    assert text.startswith("%%MatrixMarket matrix coordinate real general")
    assert "% proxy test matrix" in text
    buf.seek(0)
    np.testing.assert_allclose(read_mtx_csr(buf).to_dense(), a, atol=1e-6)


def test_mtx_symmetric_expansion(rng):
    m = generate("poisson3d_16")
    buf = io.StringIO()
    write_mtx(buf, m, symmetric="auto")
    text = buf.getvalue()
    assert "coordinate real symmetric" in text
    # lower triangle only on disk: fewer stored entries than nnz
    stored = int(text.splitlines()[1].split()[2])
    assert stored < m.nnz
    buf.seek(0)
    back = read_mtx_csr(buf)
    x = np.random.default_rng(3).standard_normal(m.shape[0]).astype(np.float32)
    np.testing.assert_allclose(back.matvec(x), m.matvec(x), rtol=1e-5,
                               atol=1e-4)


def test_mtx_pattern_and_skew():
    mtx = "%%MatrixMarket matrix coordinate pattern symmetric\n3 3 2\n2 1\n3 3\n"
    coo = read_mtx(io.StringIO(mtx))
    d = coo.to_dense()
    assert d[1, 0] == 1.0 and d[0, 1] == 1.0 and d[2, 2] == 1.0
    mtx = "%%MatrixMarket matrix coordinate real skew-symmetric\n2 2 1\n2 1 3.5\n"
    d = read_mtx(io.StringIO(mtx)).to_dense()
    assert d[1, 0] == 3.5 and d[0, 1] == -3.5


def test_mtx_rejects_unsupported():
    with pytest.raises(ValueError, match="layout"):
        read_mtx(io.StringIO("%%MatrixMarket matrix array real general\n"))
    with pytest.raises(ValueError, match="field"):
        read_mtx(io.StringIO(
            "%%MatrixMarket matrix coordinate complex general\n1 1 0\n"))


# -- registry: SpMV oracle equivalence over every generator -------------------

@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_registry_spmv_matches_oracle(name):
    """Acceptance gate: spmv_sell (interpret mode) == exact CSR matvec on
    every registry dataset; the ELL kernel agrees too."""
    csr = generate(name)
    n = csr.shape[0]
    sell = csr.to_sell(c=32, sigma=256)
    x = np.asarray(jax.random.normal(KEY, (n,), jnp.float32))
    want = csr.matvec(x).astype(np.float32)
    scale = max(1.0, float(np.abs(want).max()))

    op = ops.SellOperator.from_matrix(sell)
    got_sell = np.asarray(op.matvec(jnp.asarray(x)))
    np.testing.assert_allclose(got_sell / scale, want / scale, atol=2e-6)

    ell = csr.to_ell()
    got_ell = np.asarray(ops.spmv(jnp.asarray(ell.data),
                                  jnp.asarray(ell.cols), jnp.asarray(x)))
    np.testing.assert_allclose(got_ell / scale, want / scale, atol=2e-6)


def test_sell_kernel_matches_ref_oracle():
    """kernels/spmv_sell (fixed-window + masking) == ref.spmv_sell
    (exact per-slice widths), including the permuted padded layout."""
    csr = generate("fem_band_8k")
    sell = csr.to_sell(c=8, sigma=64)
    x = jax.random.normal(KEY, (csr.shape[0],), jnp.float32)
    got = ops.spmv_sell(jnp.asarray(sell.data), jnp.asarray(sell.cols),
                        jnp.asarray(sell.slice_offsets),
                        jnp.asarray(sell.slice_k), x,
                        c=sell.c, k_max=sell.k_max)
    want = ref.spmv_sell(jnp.asarray(sell.data), jnp.asarray(sell.cols),
                         sell.slice_offsets, sell.slice_k, x, c=sell.c)
    scale = max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=2e-6)


def test_sell_fill_beats_ell_on_irregular():
    """Acceptance gate: SELL-C-σ strictly out-fills ELL on every
    irregular (non-banded) registry dataset."""
    assert len(irregular_names()) >= 3
    for name in irregular_names():
        csr = generate(name)
        er = csr.to_ell().padding_report()
        sr = csr.to_sell(c=32, sigma=256).padding_report()
        assert sr.fill_ratio > er.fill_ratio, name
        assert sr.bytes < er.bytes, name


def test_choose_format_prefers_sell_only_when_it_pays():
    assert choose_format(generate("graph_powerlaw_8k"))[0] == "sell"
    assert choose_format(generate("poisson2d_small"))[0] == "ell"


def test_padding_report_accounting(rng):
    a = _random_sparse(rng, 64, 64)
    csr = CSRMatrix.from_dense(a)
    rep = csr.to_ell().padding_report()
    assert rep.nnz == csr.nnz
    assert 0.0 < rep.fill_ratio <= 1.0
    assert rep.csr_bytes == csr.nnz * 8 + 65 * 4
    assert rep.bytes == rep.stored * 8


# -- CG on SELL ---------------------------------------------------------------

def test_cg_sell_matches_ell_device_loop():
    csr = generate("fem_band_8k")
    ell = csr.to_ell()
    op = ops.SellOperator.from_matrix(csr.to_sell(c=32, sigma=256))
    b = jax.random.normal(KEY, (csr.shape[0],), jnp.float32)
    loop = Plan(tier="device_loop")
    x_e, rr_e = execute(CGProblem.from_ell(jnp.asarray(ell.data),
                                           jnp.asarray(ell.cols), b, 20),
                        loop)
    x_s, rr_s = execute(CGProblem.from_matvec(op.matvec, b, 20,
                                              matrix=op.matrix), loop)
    scale = float(jnp.abs(x_e).max())
    assert float(jnp.abs(x_s - x_e).max()) / scale < 1e-4
    assert abs(float(rr_s) - float(rr_e)) <= 1e-3 * (float(rr_e) + 1e-12)
    bb = float(jnp.vdot(b, b))
    assert float(rr_s) < 1e-2 * bb        # actually converging


def test_plan_policy_uses_true_nnz():
    """A pathologically padded ELL must not distort the planner: the
    matrix container path feeds true nnz (power-law ELL stores 37x its
    real nonzeros)."""
    csr = generate("graph_powerlaw_8k")
    ell = csr.to_ell()
    padded_slots = int(ell.data.size)
    assert padded_slots > 10 * csr.nnz
    budget = csr.shape[0] * 4 * 4 + csr.nnz * 8 + 1024
    true_plan = cg_policy_from_arrays(cg_arrays_for(csr), budget)
    padded_plan = cg_policy_from_arrays(
        cg_arrays(csr.shape[0], padded_slots, 4), budget)
    assert true_plan["policy"] == "MIX"
    assert true_plan["matrix_fraction"] == 1.0
    assert padded_plan["matrix_fraction"] < 0.2


# -- nnz-balanced partitioning ------------------------------------------------

@pytest.mark.parametrize("parts", [2, 4, 8, 13])
def test_partition_balance_bound(parts):
    csr = generate("graph_powerlaw_8k")
    lens = csr.row_nnz
    bounds = nnz_balanced_partition(lens, parts)
    assert bounds[0] == 0 and bounds[-1] == csr.shape[0]
    assert np.all(np.diff(bounds) >= 0)
    per = partition_nnz(bounds, lens)
    assert per.sum() == csr.nnz
    # the greedy guarantee: no part overshoots the ideal share by more
    # than one row
    assert per.max() <= csr.nnz / parts + lens.max()
    # and it beats naive equal-rows sharding on this power-law matrix
    eq = np.linspace(0, csr.shape[0], parts + 1).astype(np.int64)
    assert balance_report(bounds, lens)["imbalance"] < \
        balance_report(eq, lens)["imbalance"]


def test_partition_rejects_bad_parts():
    with pytest.raises(ValueError):
        nnz_balanced_partition(np.ones(4, np.int64), 5)
    with pytest.raises(ValueError):
        nnz_balanced_partition(np.ones(4, np.int64), 0)


def test_shard_by_nnz_preserves_spmv(rng):
    """Padded, remapped shards compute the same SpMV (and thus the same
    CG) as the original ordering."""
    csr = generate("rand_shift_16k")
    ell = csr.to_ell()
    b = rng.standard_normal(csr.shape[0]).astype(np.float32)
    sh = shard_by_nnz(ell.data, ell.cols, b, 8)
    assert sh.data.shape[0] == 8 * sh.rows_per_part
    x = rng.standard_normal(csr.shape[0]).astype(np.float32)
    x_pad = np.zeros(sh.data.shape[0], np.float32)
    x_pad[sh.pos] = x
    y_pad = (sh.data * x_pad[sh.cols]).sum(axis=1)
    np.testing.assert_allclose(y_pad[sh.pos], csr.matvec(x), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_allclose(sh.b[sh.pos], b)
    # per-shard nnz is balanced to the greedy bound
    per_shard = (sh.data.reshape(8, sh.rows_per_part, -1) != 0).sum((1, 2))
    assert per_shard.max() <= csr.nnz / 8 + csr.row_nnz.max()


# -- the nonsymmetric suite (BiCGStab/GMRES territory) -------------------------

def test_nonsymmetric_registry_tags():
    assert set(nonsymmetric_names()) == \
        {"convdiff_small", "convdiff_16k", "skew_shift_8k"}
    assert set(symmetric_names()) | set(nonsymmetric_names()) == set(REGISTRY)
    assert not (set(symmetric_names()) & set(nonsymmetric_names()))


@pytest.mark.parametrize("name", ["convdiff_small", "skew_shift_8k"])
def test_nonsymmetric_format_roundtrip(name):
    """CSR -> ELL and CSR -> SELL reproduce the dense operator exactly
    (the formats only reshuffle slots; no arithmetic)."""
    csr = generate(name)
    dense = csr.to_dense()
    np.testing.assert_array_equal(csr.to_ell().to_dense(), dense)
    sell = csr.to_sell(c=8, sigma=64)
    np.testing.assert_array_equal(sell.to_dense(), dense)


def test_convdiff_spectrum_sanity():
    """Upwind convection-diffusion: genuinely nonsymmetric, strictly
    diagonally dominant (upwinding's M-matrix property), symmetric part
    positive definite — the class BiCGStab/GMRES theory covers."""
    A = generate("convdiff_small").to_dense().astype(np.float64)
    asym = A - A.T
    assert np.abs(asym).max() > 0.1            # truly nonsymmetric
    diag = np.abs(np.diag(A))
    off = np.abs(A).sum(axis=1) - diag
    assert (diag > off).all()                  # strict diagonal dominance
    sym_eigs = np.linalg.eigvalsh((A + A.T) / 2)
    assert sym_eigs.min() > 0                  # definite symmetric part


def test_skew_shift_spectrum_sanity():
    """shift*I + (R - R^T): the symmetric part is EXACTLY shift*I, so
    every eigenvalue has real part == shift — the cleanest certificate
    that the field of values stays in the right half plane."""
    spec = REGISTRY["skew_shift_8k"]
    A = skew_shifted_random(n=512, row_nnz=spec.kwargs["row_nnz"]) \
        .to_dense().astype(np.float64)
    shift = 4.0
    sym = (A + A.T) / 2
    np.testing.assert_allclose(sym, shift * np.eye(512), atol=1e-12)
    assert np.abs(A - A.T).max() > 0.1
    eigs = np.linalg.eigvals(A)
    np.testing.assert_allclose(eigs.real, shift, atol=1e-8)


def test_nonsymmetric_entries_straddle_proxy_vmem():
    """Same sizing story as the SPD suite: the _small entry's vector
    working set fits the 256 KiB proxy VMEM, the _16k one overflows it
    (forcing the IMP regime), and the matrix itself never fits."""
    small = generate("convdiff_small")
    big = generate("convdiff_16k")
    vec = lambda csr: 4 * csr.shape[0]
    assert 7 * vec(small) < PROXY_ONCHIP_BYTES      # BiCGStab's 7 vectors
    assert 7 * vec(big) > PROXY_ONCHIP_BYTES
    assert big.nnz * 8 > PROXY_ONCHIP_BYTES


def test_sell_operator_threads_true_nnz_to_planner():
    """A SELL operator carries its source container, and a CGProblem built
    on its matvec with ``matrix=`` ranks A by the container's TRUE nnz,
    not its padded slots and not zero (without the container A would be
    absent from the knapsack entirely)."""
    op = ops.load_sell("fem_band_8k")
    assert op.matrix is not None
    b = np.random.default_rng(0).standard_normal(op.n_rows).astype(np.float32)
    prob = CGProblem.from_matvec(op.matvec, jnp.asarray(b), 2,
                                 matrix=op.matrix)
    x, rr = execute(prob, Plan(tier="device_loop"))
    assert np.isfinite(np.asarray(x)).all() and np.isfinite(float(rr))
    assert prob.matrix is op.matrix
    a_entry = {a.name: a for a in prob.cacheable_arrays()}["A"]
    true_a = {a.name: a for a in cg_arrays_for(op.matrix)}["A"]
    assert a_entry.bytes == true_a.bytes > 0
    # padded SELL slots would overstate A: true nnz must be strictly less
    padded = op.data.shape[0] * (op.data.dtype.itemsize + 4)
    assert a_entry.bytes < padded
