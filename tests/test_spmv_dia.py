"""The DIA SpMV (kernels/spmv_dia.py) and CG's choice of it.

CG's loop tiers take the gather-free DIA matvec where the ELL planes'
nonzeros lie on few diagonals (``D * value bytes <= K * (value + index
bytes)``) and keep the ELL gather otherwise. The DIA matvec is the same
SpMV on the same nonzeros, summed in another order; operators that keep
the gather run exactly the code they ran before.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.exec import BatchedProblem, CGProblem, Plan, execute
from repro.exec import execute_sequential
from repro.exec import adapters
from repro.kernels import ref as kref
from repro.kernels.spmv_dia import ell_to_dia, spmv_dia
from repro.kernels.ops import load_dataset
from repro.sparse import load_matrix
from repro.sparse.generate import poisson2d


def _x(n, seed=0):
    return jax.random.normal(jax.random.key(seed), (n,), jnp.float32)


def _gather_problem(data, cols, b, iters, **kw):
    """The same CG on the ELL gather, whatever the operator's structure."""
    return CGProblem.from_matvec(
        functools.partial(kref.spmv_ell, data, cols), b, iters, **kw)


def _steps(problem, plan):
    reg = obs.MetricsRegistry()
    with obs.use_metrics(reg):
        out = execute(problem, plan)
    return out, reg.value("executor_steps_total", tier=plan.tier)


def _true_residual(name, b, x):
    b = np.asarray(b, np.float64)
    r = b - load_matrix(name).matvec(np.asarray(x, np.float64))
    return np.linalg.norm(r) / np.linalg.norm(b)


# -- the conversion and the matvec --------------------------------------------

@pytest.mark.parametrize("name,n_diagonals", [
    ("poisson_64", 5), ("poisson3d_16", 7), ("banded_4k", 9),
    ("fem_band_8k", 33)])
def test_dia_matches_gather(name, n_diagonals):
    data, cols = load_dataset(name)
    offsets, planes = ell_to_dia(data, cols)
    assert len(offsets) == n_diagonals == planes.shape[0]
    x = _x(data.shape[0])
    want = np.asarray(kref.spmv_ell(data, cols, x))
    got = np.asarray(spmv_dia(jnp.asarray(planes), offsets, x))
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


def test_border_rows_have_no_absent_neighbours():
    """Rows on the grid's border lack neighbours: their planes are zero
    there, and the zero-padded shifts add nothing."""
    side = 6
    csr = poisson2d(side)
    ell = csr.to_ell()
    offsets, planes = ell_to_dia(ell.data, ell.cols)
    assert offsets == (-side, -1, 0, 1, side)
    rows = np.arange(side * side)
    up, left, right, down = (planes[offsets.index(d)]
                             for d in (-side, -1, 1, side))
    assert np.all(up[:side] == 0) and np.all(up[side:] == -1)
    assert np.all(down[-side:] == 0) and np.all(down[:-side] == -1)
    assert np.all(left[rows % side == 0] == 0)
    assert np.all(right[rows % side == side - 1] == 0)
    x = np.random.default_rng(0).standard_normal(side * side).astype(
        np.float32)
    want = csr.to_dense().astype(np.float64) @ x
    got = np.asarray(spmv_dia(jnp.asarray(planes), offsets, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_padding_slots_create_no_diagonal():
    """ELL padding stores data 0 at column 0; counted, it would put one
    diagonal ``-i`` on every border row."""
    data, cols = load_dataset("poisson_64")
    pad = np.asarray(data) == 0
    assert pad.any() and np.all(np.asarray(cols)[pad] == 0)
    offsets, planes = ell_to_dia(data, cols)
    assert offsets == (-64, -1, 0, 1, 64)
    assert planes.dtype == np.float32 and planes.shape == (5, 64 * 64)


def test_duplicate_slots_sum_as_the_gather_sums_them():
    data = np.array([[1.0, 2.0], [3.0, 0.0]], np.float32)
    cols = np.array([[1, 1], [0, 0]], np.int32)
    offsets, planes = ell_to_dia(data, cols)
    assert offsets == (-1, 1)
    x = jnp.array([5.0, 7.0])
    np.testing.assert_array_equal(
        np.asarray(spmv_dia(jnp.asarray(planes), offsets, x)),
        np.asarray(kref.spmv_ell(data, cols, x)))


@pytest.mark.parametrize("cols", [[[0, 2]], [[0, -1]]])
def test_columns_outside_the_operator_keep_the_gather(cols):
    """The gather clamps or wraps such a column; DIA would read zero."""
    data = np.ones((1, 2), np.float32)
    assert ell_to_dia(np.repeat(data, 2, 0),
                      np.array(cols * 2, np.int32)) is None


# -- the choice ---------------------------------------------------------------

@pytest.mark.parametrize("name", ["graph_regular_4k", "graph_powerlaw_8k",
                                  "rand_shift_16k"])
def test_unstructured_operators_keep_the_gather_bit_for_bit(name):
    data, cols = load_dataset(name)
    assert ell_to_dia(data, cols) is None
    b = _x(data.shape[0], 1)
    prob = CGProblem.from_ell(data, cols, b, 20)
    assert prob.spmv_format == "ell"
    pl = Plan(tier="device_loop")
    got = execute(prob, pl)
    want = execute(_gather_problem(data, cols, b, 20), pl)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_abstract_operands_keep_the_gather():
    data, cols = load_dataset("poisson_64")
    n = data.shape[0]
    reg = obs.MetricsRegistry()
    with obs.use_metrics(reg):
        probe = CGProblem.from_ell(
            jax.ShapeDtypeStruct(data.shape, data.dtype),
            jax.ShapeDtypeStruct(cols.shape, cols.dtype),
            jax.ShapeDtypeStruct((n,), jnp.float32), 10)
        assert probe.spmv_format == "ell"
        seen = []

        @jax.jit
        def traced(d, c, b):
            prob = CGProblem.from_ell(d, c, b, 10)
            seen.append(prob.spmv_format)
            return prob.step_fn()(prob.initial_state())

        traced(data, cols, _x(n))
        assert seen == ["ell"]
        assert _gather_problem(data, cols, _x(n), 10).spmv_format is None
    assert reg.value("spmv_dia_conversions_total") == 0


# -- convergence --------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_dia_cg_converges_as_the_gather_does(seed):
    """CG to 1e-10 on the DIA path: within one iteration of the gather
    path (exact counts from a host sync after every step), within one
    chunk at ``sync_every`` 25, and the true residual inside the same
    bound."""
    data, cols = load_dataset("poisson_64")
    b = _x(data.shape[0], seed)
    dia = CGProblem.from_ell(data, cols, b, 1000, tol=1e-10)
    ell = _gather_problem(data, cols, b, 1000, tol=1e-10)
    assert dia.spmv_format == "dia"
    bound = 5 * np.sqrt(1e-10)
    for pl, slack in ((Plan(tier="host_loop"), 1),
                      (Plan(tier="device_loop", sync_every=25), 25)):
        (x_dia, _), n_dia = _steps(dia, pl)
        (x_ell, _), n_ell = _steps(ell, pl)
        assert n_dia < 1000 and abs(n_dia - n_ell) <= slack
        assert _true_residual("poisson_64", b, x_dia) < bound
        assert _true_residual("poisson_64", b, x_ell) < bound


def test_batched_dia_cg_matches_sequential():
    """The batched tier's contract (tests/test_batch.py), on the DIA
    path: B right-hand sides in one vmapped dispatch compute what B
    sequential solves compute, bit for bit (a fixed step count: with a
    tolerance the batch runs until its slowest lane converges)."""
    data, cols = load_dataset("poisson_64")
    insts = [CGProblem.from_ell(data, cols, _x(data.shape[0], 40 + i), 30)
             for i in range(3)]
    bp = BatchedProblem.from_instances(insts)
    assert bp.spmv_format == "dia"
    for single in (Plan(tier="host_loop"),
                   Plan(tier="device_loop", sync_every=10)):
        out = execute(bp, dataclasses.replace(single, batch=3))
        for i, want in enumerate(execute_sequential(insts, single)):
            for g, w in zip(jax.tree.map(lambda a: a[i], out), want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- engagement ---------------------------------------------------------------

def test_conversion_is_once_per_operator():
    """A solver builds a fresh problem per right-hand side: the operator
    is converted once, each execution counts its format, and another
    operator of the same shape is converted anew and not aliased."""
    data, cols = load_dataset("poisson_64")
    n, runs = data.shape[0], 4
    reg = obs.MetricsRegistry()
    tr = obs.Tracer()
    pl = Plan(tier="device_loop")
    with obs.use_metrics(reg), obs.use_tracer(tr):
        for i in range(runs):
            execute(CGProblem.from_ell(data, cols, _x(n, i), 5), pl)
        assert reg.value("spmv_dia_conversions_total") == 1
        assert reg.value("executor_spmv_total", format="dia") == runs
        assert [dict(e.args)["spmv"] for e in tr.by_cat("dispatch")] == \
            ["dia"] * runs

        data2 = data * 2.0
        b = _x(n, 9)
        x2, _ = execute(CGProblem.from_ell(data2, cols, b, 5), pl)
        assert reg.value("spmv_dia_conversions_total") == 2
        x_gather, _ = execute(_gather_problem(data2, cols, b, 5), pl)
        np.testing.assert_allclose(np.asarray(x2), np.asarray(x_gather),
                                   rtol=1e-5, atol=1e-6)
        x1, _ = execute(CGProblem.from_ell(data, cols, b, 5), pl)
        np.testing.assert_allclose(np.asarray(x1), 2 * np.asarray(x2),
                                   rtol=1e-5, atol=1e-6)
        assert reg.value("spmv_dia_conversions_total") == 2
        assert reg.value("executor_spmv_total", format="dia") == runs + 2
        assert reg.value("executor_spmv_total", format="ell") == 0


def test_conversion_cache_is_bounded_and_pins_its_operands():
    data, cols = load_dataset("poisson_64")
    ops = [(data * (i + 1.0), cols) for i in range(adapters._DIA_CACHE_SIZE
                                                  + 3)]
    for d, c in ops:
        assert adapters.dia_operator(d, c) is not None
    assert len(adapters._DIA_CACHE) == adapters._DIA_CACHE_SIZE
    for d, c, _ in adapters._DIA_CACHE.values():
        assert any(d is od for od, _ in ops) and c is cols
