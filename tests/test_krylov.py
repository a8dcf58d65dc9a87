"""Property-based verification of the Krylov family (exec/krylov.py).

Every solver the executor serves — CG, BiCGStab, GMRES(m), s-step CG —
is checked here against dense float64 NumPy references that share *no
code* with the jax implementations, over randomized SPD and nonsymmetric
operators and the nonsymmetric ``repro.sparse`` registry entries.

The contracts (DESIGN.md §10):

  * NumPy-oracle agreement: the f32 jax solve at matched iteration
    count tracks the f64 dense reference to single-precision accuracy.
  * Residual invariants — each method's *own* guarantee, not a generic
    monotonicity that none of them has: CG's A-norm error is
    non-increasing (its residual 2-norm is NOT monotone); GMRES(m)'s
    residual is non-increasing across restart cycles (it minimizes it
    over a growing affine space each cycle); BiCGStab converges on
    diagonally dominant systems but may spike in between, so only its
    endpoint is bounded.
  * s-step CG == standard CG at matched cadence: the coefficient-space
    recurrence is algebraically textbook CG; in f32 monomial-basis
    conditioning costs a few digits, so the tolerance is looser but the
    iteration count is exact (including non-dividing tails).
  * Tier and batch bit-exactness: host_loop == device_loop exactly for
    every new solver; B-wide batched == B sequential solves exactly for
    BiCGStab, and to the last f32 ulp for GMRES (whose per-cycle lstsq
    lowers to a batched SVD under vmap — see the batched test).
  * Mixed precision: the compensated dot tracks the f64 dot where the
    naive f32 dot loses digits, and iterative refinement strictly
    improves the residual on an ill-conditioned solve.

Property tests are thin wrappers over deterministic ``_check_*``
helpers via the optional-hypothesis shim (``_hyp.py``) — with
hypothesis absent they skip; the deterministic tests pin fixed seeds so
tier-1 coverage never depends on the optional dep.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.exec import (
    BatchedProblem,
    BiCGStabProblem,
    CGProblem,
    GMRESProblem,
    Plan,
    cg_sstep_run,
    compensated_vdot,
    execute,
    execute_sequential,
    plan,
    solve_refined,
)
from repro.kernels import ref as kref
from repro.sparse.generate import (
    banded_spd,
    convdiff2d,
    nonsymmetric_names,
    skew_shifted_random,
)

# =============================================================================
# dense float64 references (no shared code with repro.kernels.ref)
# =============================================================================

def np_cg(A, b, iters):
    """Textbook CG in f64. Returns (x, rr, anorm_err_history)."""
    A = np.asarray(A, np.float64)
    b = np.asarray(b, np.float64)
    x_star = np.linalg.solve(A, b)
    x = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rr = float(r @ r)
    errs = []
    for _ in range(iters):
        e = x - x_star
        errs.append(float(e @ (A @ e)))
        ap = A @ p
        alpha = rr / (p @ ap) if p @ ap != 0 else 0.0
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = float(r @ r)
        beta = rr_new / rr if rr != 0 else 0.0
        p = r + beta * p
        rr = rr_new
    e = x - x_star
    errs.append(float(e @ (A @ e)))
    return x, rr, errs


def np_bicgstab(A, b, iters):
    """van der Vorst BiCGStab in f64. Returns (x, rr)."""
    A = np.asarray(A, np.float64)
    b = np.asarray(b, np.float64)
    x = np.zeros_like(b)
    r = b.copy()
    rhat = r.copy()
    p = np.zeros_like(b)
    v = np.zeros_like(b)
    rho = alpha = omega = 1.0

    def div(a, c):
        return a / c if c != 0 else 0.0

    for _ in range(iters):
        rho_new = float(rhat @ r)
        beta = div(rho_new, rho) * div(alpha, omega)
        p = r + beta * (p - omega * v)
        v = A @ p
        alpha = div(rho_new, float(rhat @ v))
        s = r - alpha * v
        t = A @ s
        omega = div(float(t @ s), float(t @ t))
        x = x + alpha * p + omega * s
        r = s - omega * t
        rho = rho_new
    return x, float(r @ r)


def np_gmres(A, b, cycles, m):
    """Restarted GMRES(m) in f64 (modified Gram-Schmidt Arnoldi).
    Returns (x, rr_per_cycle) — rr_per_cycle[k] is ||b - A x||^2 after
    cycle k (length cycles)."""
    A = np.asarray(A, np.float64)
    b = np.asarray(b, np.float64)
    n = b.shape[0]
    x = np.zeros_like(b)
    rrs = []
    for _ in range(cycles):
        r = b - A @ x
        beta = np.linalg.norm(r)
        if beta == 0:
            rrs.append(0.0)
            continue
        V = np.zeros((m + 1, n))
        H = np.zeros((m + 1, m))
        V[0] = r / beta
        for j in range(m):
            w = A @ V[j]
            for i in range(j + 1):
                H[i, j] = V[i] @ w
                w = w - H[i, j] * V[i]
            H[j + 1, j] = np.linalg.norm(w)
            if H[j + 1, j] != 0:
                V[j + 1] = w / H[j + 1, j]
        e1 = np.zeros(m + 1)
        e1[0] = beta
        y, *_ = np.linalg.lstsq(H, e1, rcond=None)
        x = x + y @ V[:m]
        rr = b - A @ x
        rrs.append(float(rr @ rr))
    return x, rrs


# =============================================================================
# operator builders
# =============================================================================

def _spd_ell(n=192, bands=4, seed=0):
    mat = banded_spd(n, bands, seed=seed)
    ell = mat.to_ell()
    return (jnp.asarray(ell.data), jnp.asarray(ell.cols),
            mat.to_dense().astype(np.float64))


def _nonsym_ell(name):
    builders = {
        "convdiff": lambda: convdiff2d(side=16),
        "skew": lambda: skew_shifted_random(512, row_nnz=5, shift=6.0,
                                            seed=3),
    }
    mat = builders[name]()
    ell = mat.to_ell()
    return (jnp.asarray(ell.data), jnp.asarray(ell.cols),
            mat.to_dense().astype(np.float64))


def _random_spd_dense(n, seed):
    """Well-conditioned random SPD: Q diag(1..4) Q^T."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    d = np.linspace(1.0, 4.0, n)
    return (q * d) @ q.T


def _random_diagdom_dense(n, seed):
    """Random nonsymmetric strictly diagonally dominant matrix."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, n)) * 0.5
    np.fill_diagonal(A, np.abs(A).sum(axis=1) + 1.0)
    return A


def _dense_to_ell(A):
    """Dense -> full-width ELL planes (every column a 'nonzero')."""
    n = A.shape[0]
    data = jnp.asarray(A, jnp.float32)
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (n, n))
    return data, cols


def _rhs(n, seed=1):
    return jax.random.normal(jax.random.key(seed), (n,), jnp.float32)


# =============================================================================
# NumPy-oracle agreement (deterministic; property tests wrap these)
# =============================================================================

def _check_bicgstab_matches_numpy(A, iters=25, rel=5e-4):
    data, cols = _dense_to_ell(A)
    n = A.shape[0]
    b = _rhs(n)
    x_np, _ = np_bicgstab(A, np.asarray(b, np.float64), iters)
    prob = BiCGStabProblem.from_ell(data, cols, b, iters)
    x, rr = execute(prob, Plan(tier="host_loop"))
    scale = max(float(np.abs(x_np).max()), 1e-12)
    assert float(jnp.abs(x - x_np).max()) / scale < rel
    assert float(rr) < rel * float(jnp.vdot(b, b))


def _check_gmres_matches_numpy(A, cycles=3, m=10, rel=5e-4):
    data, cols = _dense_to_ell(A)
    n = A.shape[0]
    b = _rhs(n)
    x_np, rrs = np_gmres(A, np.asarray(b, np.float64), cycles, m)
    prob = GMRESProblem.from_ell(data, cols, b, cycles, m=m)
    x, rr = execute(prob, Plan(tier="host_loop"))
    scale = max(float(np.abs(x_np).max()), 1e-12)
    assert float(jnp.abs(x - x_np).max()) / scale < rel
    # the jax residual lands within noise of the f64 cycle residual
    assert float(rr) <= rrs[-1] + rel * float(jnp.vdot(b, b))


def _check_cg_matches_numpy(A, iters=30, rel=5e-4):
    data, cols = _dense_to_ell(A)
    n = A.shape[0]
    b = _rhs(n)
    x_np, _, _ = np_cg(A, np.asarray(b, np.float64), iters)
    x, rr = execute(CGProblem.from_ell(data, cols, b, iters),
                    Plan(tier="host_loop"))
    scale = max(float(np.abs(x_np).max()), 1e-12)
    assert float(jnp.abs(x - x_np).max()) / scale < rel


def test_cg_matches_numpy_on_random_spd():
    _check_cg_matches_numpy(_random_spd_dense(96, seed=0))


def test_bicgstab_matches_numpy_on_random_spd():
    _check_bicgstab_matches_numpy(_random_spd_dense(96, seed=1))


def test_bicgstab_matches_numpy_on_diagdom_nonsym():
    _check_bicgstab_matches_numpy(_random_diagdom_dense(96, seed=2))


def test_gmres_matches_numpy_on_random_spd():
    _check_gmres_matches_numpy(_random_spd_dense(96, seed=3))


def test_gmres_matches_numpy_on_diagdom_nonsym():
    _check_gmres_matches_numpy(_random_diagdom_dense(96, seed=4))


@given(seed=st.integers(min_value=0, max_value=40))
@settings(max_examples=12, deadline=None)
def test_property_bicgstab_tracks_f64_reference(seed):
    """Random diag-dominant operators: BiCGStab tracks the dense f64
    reference at matched iteration count."""
    _check_bicgstab_matches_numpy(_random_diagdom_dense(64, seed=seed),
                                  iters=20)


@given(seed=st.integers(min_value=0, max_value=40))
@settings(max_examples=12, deadline=None)
def test_property_gmres_tracks_f64_reference(seed):
    _check_gmres_matches_numpy(_random_diagdom_dense(64, seed=seed),
                               cycles=2, m=12)


@given(seed=st.integers(min_value=0, max_value=40))
@settings(max_examples=12, deadline=None)
def test_property_cg_tracks_f64_reference(seed):
    _check_cg_matches_numpy(_random_spd_dense(64, seed=seed), iters=25)


# =============================================================================
# residual contracts (each method's own invariant)
# =============================================================================

def test_cg_anorm_error_nonincreasing():
    """CG minimizes the A-norm of the error over the growing Krylov space
    — THAT is monotone; the residual 2-norm is not (and the suite must
    not pretend it is)."""
    A = _random_spd_dense(96, seed=5)
    b = np.asarray(_rhs(96), np.float64)
    _, _, errs = np_cg(A, b, 30)
    for k in range(1, len(errs)):
        assert errs[k] <= errs[k - 1] * (1 + 1e-9), (k, errs[k - 1], errs[k])


def _check_gmres_rr_nonincreasing(A, cycles=4, m=8):
    data, cols = _dense_to_ell(A)
    n = A.shape[0]
    b = _rhs(n)
    prob = GMRESProblem.from_ell(data, cols, b, 1, m=m)
    step = prob.step_fn()
    state = prob.initial_state()
    rrs = [float(state[1])]
    for _ in range(cycles):
        state = step(state)
        rrs.append(float(state[1]))
    for k in range(1, len(rrs)):
        # non-increasing up to f32 roundoff on the explicit recompute
        assert rrs[k] <= rrs[k - 1] * (1 + 1e-4) + 1e-10 * rrs[0], rrs


def test_gmres_rr_nonincreasing_across_restarts():
    _check_gmres_rr_nonincreasing(_random_diagdom_dense(96, seed=6))


@given(seed=st.integers(min_value=0, max_value=40))
@settings(max_examples=12, deadline=None)
def test_property_gmres_rr_nonincreasing(seed):
    """GMRES minimizes ||b - A x|| each cycle over a space containing the
    previous iterate — the residual can never go up at a restart."""
    _check_gmres_rr_nonincreasing(_random_diagdom_dense(64, seed=seed),
                                  cycles=3, m=6)


def _check_bicgstab_converges_diagdom(seed, n=64, iters=25):
    A = _random_diagdom_dense(n, seed=seed)
    data, cols = _dense_to_ell(A)
    b = _rhs(n)
    _, rr = execute(BiCGStabProblem.from_ell(data, cols, b, iters),
                    Plan(tier="host_loop"))
    assert float(rr) < 1e-6 * float(jnp.vdot(b, b)), float(rr)


@given(seed=st.integers(min_value=0, max_value=40))
@settings(max_examples=12, deadline=None)
def test_property_bicgstab_converges_on_diagdom(seed):
    """Endpoint contract only: BiCGStab residuals may spike mid-solve."""
    _check_bicgstab_converges_diagdom(seed)


def test_bicgstab_converged_state_is_fixed_point():
    """Past convergence the safe-division guards must hold the state
    steady instead of producing NaNs (same contract CG carries)."""
    data, cols, _ = _spd_ell(n=128, bands=3, seed=7)
    b = _rhs(128)
    x40, rr40 = execute(BiCGStabProblem.from_ell(data, cols, b, 40),
                        Plan(tier="host_loop"))
    x80, rr80 = execute(BiCGStabProblem.from_ell(data, cols, b, 80),
                        Plan(tier="host_loop"))
    assert np.isfinite(np.asarray(x80)).all()
    assert float(rr80) <= max(float(rr40), 1e-8 * float(jnp.vdot(b, b)))


# =============================================================================
# registry operators (the sparse path end to end)
# =============================================================================

def test_nonsymmetric_registry_names():
    assert {"convdiff_small", "convdiff_16k", "skew_shift_8k"} <= \
        set(nonsymmetric_names())


@pytest.mark.parametrize("name", ["convdiff", "skew"])
def test_bicgstab_converges_on_nonsymmetric_registry(name):
    data, cols, A = _nonsym_ell(name)
    n = data.shape[0]
    b = _rhs(n)
    iters = 60
    x, rr = execute(BiCGStabProblem.from_ell(data, cols, b, iters,
                                             tol=1e-10),
                    Plan(tier="device_loop", sync_every=20))
    x_np, _ = np_bicgstab(A, np.asarray(b, np.float64), iters)
    assert float(rr) < 1e-6 * float(jnp.vdot(b, b)), float(rr)
    scale = max(float(np.abs(x_np).max()), 1e-12)
    assert float(jnp.abs(x - x_np).max()) / scale < 1e-3


@pytest.mark.parametrize("name", ["convdiff", "skew"])
def test_gmres_converges_on_nonsymmetric_registry(name):
    data, cols, A = _nonsym_ell(name)
    n = data.shape[0]
    b = _rhs(n)
    x, rr = execute(GMRESProblem.from_ell(data, cols, b, 4, m=16),
                    Plan(tier="host_loop"))
    assert float(rr) < 1e-6 * float(jnp.vdot(b, b)), float(rr)
    x_np, _ = np_gmres(A, np.asarray(b, np.float64), 4, 16)
    scale = max(float(np.abs(x_np).max()), 1e-12)
    assert float(jnp.abs(x - x_np).max()) / scale < 1e-3


# =============================================================================
# s-step CG == standard CG at matched cadence
# =============================================================================

def _check_sstep_matches_cg(iters, s, n=128, seed=0, rel=1e-9):
    """Same operator, same b, same TOTAL iteration count, in f64: the
    s-step coefficient recurrence is algebraically textbook CG, so with
    the monomial-basis conditioning taken out of the picture the two
    must agree to roundoff — dividing cadences, non-dividing tails and
    s=1 (which degenerates to per-iteration CG) alike."""
    with jax.enable_x64(True):
        A = _random_spd_dense(n, seed=seed)
        data = jnp.asarray(A, jnp.float64)
        cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (n, n))
        b = jnp.asarray(np.random.default_rng(seed + 1).standard_normal(n))
        x_ref, rr_ref = kref.cg_run(data, cols, b, iters)
        x_s, rr_s = cg_sstep_run(data, cols, b, iters, s=s)
        scale = max(float(jnp.abs(x_ref).max()), 1e-12)
        assert float(jnp.abs(x_s - x_ref).max()) / scale < rel, (iters, s)
        assert abs(float(rr_s) - float(rr_ref)) <= \
            1e-6 * (float(rr_ref) + 1e-12 * float(jnp.vdot(b, b)))


@pytest.mark.parametrize("iters,s", [(8, 4), (6, 4), (13, 4), (9, 3),
                                     (5, 1), (16, 5)])
def test_sstep_cg_matches_standard_cg(iters, s):
    _check_sstep_matches_cg(iters, s)


def test_sstep_cg_tracks_cg_in_f32_preconvergence():
    """In storage precision the monomial basis costs digits (and near
    machine-zero residual it can stagnate — the classic s-step trade);
    before convergence the iterates still track standard CG."""
    data, cols, _ = _spd_ell(n=256, bands=3, seed=0)
    b = _rhs(256)
    x_ref, _ = kref.cg_run(data, cols, b, 6)
    x_s, _ = cg_sstep_run(data, cols, b, 6, s=3)
    scale = max(float(jnp.abs(x_ref).max()), 1e-12)
    assert float(jnp.abs(x_s - x_ref).max()) / scale < 1e-2


@given(iters=st.integers(min_value=1, max_value=16),
       s=st.integers(min_value=1, max_value=5))
@settings(max_examples=20, deadline=None)
def test_property_sstep_cadence_equivalence(iters, s):
    _check_sstep_matches_cg(iters, s, seed=2)


# =============================================================================
# tier equivalence and batch bit-exactness for the new solvers
# =============================================================================

def _problems(n=256, iters=8, seeds=(1, 2, 3)):
    data, cols, _ = _spd_ell(n=n, bands=4, seed=9)
    return {
        "bicgstab": [BiCGStabProblem.from_ell(data, cols, _rhs(n, s), iters)
                     for s in seeds],
        "gmres": [GMRESProblem.from_ell(data, cols, _rhs(n, s), 2, m=8)
                  for s in seeds],
    }


@pytest.mark.parametrize("kind", ["bicgstab", "gmres"])
def test_host_loop_equals_device_loop(kind):
    prob = _problems()[kind][0]
    host = execute(prob, Plan(tier="host_loop"))
    dev = execute(prob, Plan(tier="device_loop"))
    for h, d in zip(jax.tree.leaves(host), jax.tree.leaves(dev)):
        np.testing.assert_array_equal(np.asarray(h), np.asarray(d))


@pytest.mark.parametrize("kind", ["bicgstab", "gmres"])
def test_loop_tiers_match_oracle_exactly(kind):
    prob = _problems()[kind][0]
    x, rr = execute(prob, Plan(tier="host_loop"))
    x_o, rr_o = prob.oracle()
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x_o))
    np.testing.assert_array_equal(np.asarray(rr), np.asarray(rr_o))


def test_batched_bicgstab_matches_sequential_bitexact():
    insts = _problems()["bicgstab"]
    bp = BatchedProblem.from_instances(insts)
    for single in (Plan(tier="host_loop"), Plan(tier="device_loop")):
        batched = dataclasses.replace(single, batch=len(insts))
        out = execute(bp, batched)
        seq = execute_sequential(insts, single)
        for got, want in zip(bp.split(out), seq):
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_batched_gmres_matches_sequential():
    """GMRES cannot promise bit-exactness under vmap: the per-cycle
    ``jnp.linalg.lstsq`` lowers to a *batched* SVD whose reduction order
    differs from the single-instance solve. The contract is ulp-level
    agreement instead (the vectors differ in the last f32 digit only)."""
    insts = _problems()["gmres"]
    bp = BatchedProblem.from_instances(insts)
    for single in (Plan(tier="host_loop"), Plan(tier="device_loop")):
        batched = dataclasses.replace(single, batch=len(insts))
        out = execute(bp, batched)
        seq = execute_sequential(insts, single)
        for got, want in zip(bp.split(out), seq):
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["bicgstab", "gmres"])
def test_resident_tier_matches_loop(kind):
    """The fused Pallas kernels (interpret mode off-TPU) agree with the
    loop tiers to f32 reassociation tolerance."""
    prob = _problems()[kind][0]
    x, rr = execute(prob, Plan(tier="host_loop"))
    xr, rrr = execute(prob, Plan(tier="resident", policy="MIX"))
    scale = max(float(jnp.abs(x).max()), 1e-12)
    assert float(jnp.abs(xr - x).max()) / scale < 1e-4
    assert np.isfinite(float(rrr))


def test_planner_serves_new_kinds():
    for kind, probs in _problems().items():
        p = plan(probs[0])
        assert p.tier in ("host_loop", "device_loop", "resident")
        x, rr = execute(probs[0], p)
        assert np.isfinite(np.asarray(x)).all()


# =============================================================================
# mixed precision
# =============================================================================

def test_compensated_vdot_tracks_f64():
    """A cancellation-heavy sum where the naive f32 dot loses digits."""
    rng = np.random.default_rng(11)
    a = np.float32(rng.standard_normal(4096) * 1e4)
    c = np.float32(rng.standard_normal(4096))
    exact = float(np.asarray(a, np.float64) @ np.asarray(c, np.float64))
    comp = float(compensated_vdot(jnp.asarray(a), jnp.asarray(c)))
    naive = float(jnp.vdot(jnp.asarray(a), jnp.asarray(c)))
    scale = abs(exact) + 1e-12
    assert abs(comp - exact) / scale <= abs(naive - exact) / scale + 1e-9
    assert abs(comp - exact) / scale < 1e-6
    with jax.enable_x64(True):
        comp64 = float(compensated_vdot(jnp.asarray(a), jnp.asarray(c)))
    assert abs(comp64 - exact) / scale < 1e-6


@pytest.mark.parametrize("kind", ["cg", "bicgstab", "gmres"])
def test_mixed_precision_plan_dimension(kind):
    data, cols, _ = _spd_ell(n=192, bands=4, seed=12)
    b = _rhs(192)
    probs = {
        "cg": CGProblem.from_ell(data, cols, b, 10),
        "bicgstab": BiCGStabProblem.from_ell(data, cols, b, 10),
        "gmres": GMRESProblem.from_ell(data, cols, b, 2, m=8),
    }
    prob = probs[kind]
    xu, _ = execute(prob, Plan(tier="host_loop"))
    xm, rrm = execute(prob, Plan(tier="host_loop", precision="mixed"))
    scale = max(float(jnp.abs(xu).max()), 1e-12)
    assert float(jnp.abs(xm - xu).max()) / scale < 1e-3
    assert np.isfinite(float(rrm))
    # resident tier refuses the mixed dimension loudly
    with pytest.raises(NotImplementedError):
        execute(prob.with_precision("mixed"),
                Plan(tier="resident", policy="MIX"))


def test_solve_refined_improves_residual():
    data, cols, _ = _spd_ell(n=192, bands=4, seed=13)
    b = _rhs(192)
    prob = CGProblem.from_ell(data, cols, b, 12)
    _, rr0 = execute(prob, Plan(tier="host_loop"))
    _, rr2 = solve_refined(prob, Plan(tier="host_loop", precision="mixed"),
                           rounds=2)
    assert float(rr2) < float(rr0), (float(rr2), float(rr0))


# =============================================================================
# cache identity: different operators never share a runner
# =============================================================================

def test_same_shape_different_matrix_distinct_identity():
    """Two same-size problems over different operators must carry
    distinct ``name``s and ``batch_key``s — the content fingerprint is
    what keeps plan/runner caches from serving matrix A's compiled
    artifact to matrix B (satellite: solver_service regression)."""
    n = 192
    b = _rhs(n)
    d1, c1, _ = _spd_ell(n=n, bands=4, seed=20)
    d2, c2, _ = _spd_ell(n=n, bands=4, seed=21)
    for cls, extra in ((CGProblem, {}), (BiCGStabProblem, {}),
                       (GMRESProblem, {"m": 8})):
        p1 = cls.from_ell(d1, c1, b, 4, **extra)
        p2 = cls.from_ell(d2, c2, b, 4, **extra)
        assert p1.name != p2.name, cls.__name__
        assert p1.batch_key() != p2.batch_key(), cls.__name__


@given(seed=st.integers(min_value=0, max_value=60))
@settings(max_examples=15, deadline=None)
def test_property_fingerprint_separates_operators(seed):
    """Any pair of distinct random operators fingerprints differently
    (crc32 over sampled content — collisions possible in principle,
    vanishingly unlikely over this seed range, and a collision here
    would be exactly the bug the fingerprint exists to catch)."""
    n = 64
    b = _rhs(n)
    A1 = _random_diagdom_dense(n, seed=seed)
    A2 = _random_diagdom_dense(n, seed=seed + 1000)
    p1 = BiCGStabProblem.from_ell(*_dense_to_ell(A1), b, 4)
    p2 = BiCGStabProblem.from_ell(*_dense_to_ell(A2), b, 4)
    assert p1.name != p2.name
