"""SolverService: queue -> pack -> batched dispatch (DESIGN.md §8).

Packing is the correctness-critical part: requests with different batch
keys (different stencil family, different CG operator, different shapes)
must NEVER share a dispatch, FIFO must hold, padding must be invisible,
and every request's result must be bit-identical to solving it alone.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec import (BatchedProblem, BiCGStabProblem, CGProblem,
                        GMRESProblem, Plan, StencilProblem, execute)
from repro.kernels.common import get_spec
from repro.runtime.solver_service import (
    RequestResult,
    ServiceConfig,
    SolverService,
)
from repro.kernels.ops import load_dataset
from repro.sparse.generate import banded_spd

STEPS = 4


def _stencil(name, seed, shape=None):
    spec = get_spec(name)
    shape = shape or ((32, 32) if spec.ndim == 2 else (16, 12, 8))
    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return StencilProblem(x, spec, STEPS)


def _cg(data, cols, seed, iters=STEPS):
    b = jax.random.normal(jax.random.key(seed), (data.shape[0],), jnp.float32)
    return CGProblem.from_ell(data, cols, b, iters)


def _single_result(problem, plan):
    """The request solved alone under the batch's plan (same tier/knobs)."""
    return execute(problem, dataclasses.replace(plan, batch=1, cache=()))


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_mixed_specs_never_cross_batches():
    """An interleaved multi-tenant queue packs into per-key batches."""
    data, cols = load_dataset("poisson_64")
    svc = SolverService(ServiceConfig(max_batch=8))
    problems = {}
    for i in range(4):
        for p in (_stencil("2d5pt", i), _stencil("3d7pt", 10 + i),
                  _cg(data, cols, 20 + i)):
            problems[svc.submit(p)] = p
    assert svc.pending() == 12

    results = svc.drain()
    stats = svc.stats()
    assert svc.pending() == 0
    assert stats["served"] == 12
    assert stats["batches"] == 3            # one per key, none mixed
    assert stats["mean_batch_size"] == 4.0
    assert len(svc.chosen_plans()) == 3

    for rid, problem in problems.items():
        rr = results[rid]
        assert isinstance(rr, RequestResult)
        assert rr.batch_size == 4           # only same-key companions
        _assert_same(rr.result, _single_result(problem, rr.plan))


def test_different_cg_operators_do_not_share_a_batch():
    data, cols = load_dataset("poisson_64")
    data2 = data + 0.0                      # same shape, different operator
    svc = SolverService(ServiceConfig(max_batch=8))
    svc.submit(_cg(data, cols, 0))
    svc.submit(_cg(data2, cols, 1))
    svc.drain()
    assert svc.stats()["batches"] == 2


def test_padding_to_planned_width():
    svc = SolverService(ServiceConfig(max_batch=4, pad_to_max=True))
    problems = {svc.submit(_stencil("2d5pt", i)): i for i in range(3)}
    results = svc.drain()
    assert set(results) == set(problems)
    for rr in results.values():
        assert rr.batch_size == 3 and rr.padded_to == 4
        assert rr.plan.batch == 4
    assert svc.stats()["pad_fraction"] == pytest.approx(1 / 4)


def test_no_padding_mode_plans_actual_width():
    svc = SolverService(ServiceConfig(max_batch=4, pad_to_max=False))
    for i in range(3):
        svc.submit(_stencil("2d5pt", i))
    results = svc.drain()
    for rr in results.values():
        assert rr.batch_size == 3 and rr.padded_to == 3


def test_fifo_oldest_key_group_first():
    svc = SolverService(ServiceConfig(max_batch=8))
    a0 = svc.submit(_stencil("2d5pt", 0))
    b0 = svc.submit(_stencil("3d7pt", 1))
    a1 = svc.submit(_stencil("2d5pt", 2))
    first = svc.run_batch()
    assert set(first) == {a0, a1}           # oldest request's key wins
    second = svc.run_batch()
    assert set(second) == {b0}


def test_max_batch_splits_oversized_groups():
    svc = SolverService(ServiceConfig(max_batch=2))
    ids = [svc.submit(_stencil("2d5pt", i)) for i in range(5)]
    first = svc.run_batch()
    assert set(first) == set(ids[:2])       # strict FIFO within the key
    svc.drain()
    assert svc.stats()["batches"] == 3


def test_service_rejects_prebatched_submissions():
    svc = SolverService()
    bp = BatchedProblem.from_instances([_stencil("2d5pt", 0)])
    with pytest.raises(TypeError, match="single-instance"):
        svc.submit(bp)
    with pytest.raises(ValueError, match="no queued"):
        svc.run_batch()


def test_plan_is_cached_per_key_and_telemetry_accumulates():
    svc = SolverService(ServiceConfig(max_batch=2))
    for i in range(4):
        svc.submit(_stencil("2d5pt", i))
    results = svc.drain()
    stats = svc.stats()
    assert stats["batches"] == 2
    assert stats["distinct_plans"] == 1     # second batch reused the plan
    assert stats["instances_per_s"] > 0
    assert stats["mean_latency_s"] >= stats["mean_queued_s"] >= 0
    plans = {id(rr.plan) for rr in results.values()}
    assert len(plans) == 1


def test_service_respects_convergence_checks():
    """A request that declares tol gets a plan that can evaluate it (the
    service never silently drops a convergence contract) and stops
    early."""
    import warnings

    from repro.exec.executor import honors_on_sync

    data, cols = load_dataset("poisson_64")
    svc = SolverService(ServiceConfig(max_batch=2))
    bvecs = [jax.random.normal(jax.random.key(40 + i), (data.shape[0],),
                               jnp.float32) for i in range(2)]
    rids = [svc.submit(CGProblem.from_ell(data, cols, b, 500, tol=1e-10))
            for b in bvecs]
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)   # no dropped checks
        results = svc.drain()
    for rid, b in zip(rids, bvecs):
        rr_plan = results[rid].plan
        assert honors_on_sync(rr_plan, 500)
        _, rr = results[rid].result
        assert float(rr) < 1e-10 * float(jnp.vdot(b, b)) * 10


def test_loop_tier_runner_is_reused_across_batches():
    """The per-key steady-state runner serves later batches of the same
    key (new payloads, same compiled program) bit-exactly."""
    from repro.exec import execute_sequential

    svc = SolverService(ServiceConfig(max_batch=2))
    first = [_stencil("2d5pt", i) for i in range(2)]
    later = [_stencil("2d5pt", 10 + i) for i in range(2)]
    bp = BatchedProblem.from_instances(first)
    runner = svc._make_runner(bp, Plan(tier="device_loop", batch=2))
    assert runner is not None
    for batch_insts in (first, later):
        batch = BatchedProblem.from_instances(batch_insts)
        out = runner(batch)
        seq = execute_sequential(batch_insts, Plan(tier="device_loop"))
        for got, want in zip(batch.split(out), seq):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # non-loop tiers and convergence-checked problems rebuild per batch
    assert svc._make_runner(
        bp, Plan(tier="resident", batch=2, cached_rows=8)) is None
    data, cols = load_dataset("poisson_64")
    tol_bp = BatchedProblem.from_instances(
        [CGProblem.from_ell(data, cols,
                            jnp.ones((data.shape[0],), jnp.float32), 8,
                            tol=1e-8) for _ in range(2)])
    assert svc._make_runner(
        tol_bp, Plan(tier="device_loop", batch=2, sync_every=4)) is None


def test_autotuned_service_still_correct():
    svc = SolverService(ServiceConfig(max_batch=2, autotune_top_k=2))
    problems = {svc.submit(_stencil("2d5pt", i)): None for i in range(2)}
    results = svc.drain()
    assert set(results) == set(problems)
    for rr in results.values():
        assert rr.plan.batch == 2


def test_autotuned_service_also_respects_convergence_checks():
    """The autotune path measures only candidates that honor a declared
    tol — the measured-fastest plan may never drop the contract."""
    from repro.exec.executor import honors_on_sync

    data, cols = load_dataset("poisson_64")
    svc = SolverService(ServiceConfig(max_batch=2, autotune_top_k=3))
    tol_rid = svc.submit(
        CGProblem.from_ell(
            data, cols,
            jax.random.normal(jax.random.key(51), (data.shape[0],),
                              jnp.float32),
            500, tol=1e-10))
    results = svc.drain()
    assert honors_on_sync(results[tol_rid].plan, 500)


def test_cold_vs_warm_key_plan_time_is_separated():
    """Planning/autotune time is reported as plan_s on the COLD batch and
    is exactly 0.0 on warm batches — never smeared into queued_s (the
    old behavior folded it into every cold rider's queue time)."""
    ticks = iter(range(10**6))
    svc = SolverService(ServiceConfig(max_batch=2),
                        clock=lambda: float(next(ticks)))
    cold = [svc.submit(_stencil("2d5pt", i)) for i in range(2)]
    warm = [svc.submit(_stencil("2d5pt", 10 + i)) for i in range(2)]
    results = svc.drain()
    for rid in cold:
        rr = results[rid]
        assert rr.plan_s > 0.0
        # queued time ends at batch pickup, BEFORE planning: with the
        # tick clock, latency strictly exceeds queue + plan + exec only
        # by the pickup/packing instants, never the other way round
        assert rr.latency_s >= rr.queued_s + rr.plan_s + rr.exec_s
    for rid in warm:
        assert results[rid].plan_s == 0.0
        assert results[rid].queued_s >= 0.0
    assert svc.stats()["plan_s_total"] == results[cold[0]].plan_s


def test_plan_cache_pins_operator_objects():
    """The plan cache holds the template problem, so the operand ids
    inside cached batch keys cannot be garbage-collected and recycled."""
    data, cols = load_dataset("poisson_64")
    svc = SolverService(ServiceConfig(max_batch=2))
    svc.submit(_cg(data, cols, 0))
    svc.drain()
    (_, template, _), = svc._plans.values()
    assert template.data is data
    assert svc.evict_plans() == 1
    assert svc.stats()["distinct_plans"] == 0


def _two_operators(n=512):
    """Two operators with IDENTICAL shapes/dtypes but different content —
    the collision case the content fingerprint exists for."""
    out = []
    for seed in (31, 32):
        ell = banded_spd(n, 4, seed=seed).to_ell()
        out.append((jnp.asarray(ell.data), jnp.asarray(ell.cols)))
    return out


@pytest.mark.parametrize("make", [
    lambda d, c, b: CGProblem.from_ell(d, c, b, STEPS),
    lambda d, c, b: BiCGStabProblem.from_ell(d, c, b, STEPS),
    lambda d, c, b: GMRESProblem.from_ell(d, c, b, 2, m=6),
], ids=["cg", "bicgstab", "gmres"])
def test_same_size_different_matrix_never_shares_runner(make):
    """Two same-shaped requests over different operators must resolve to
    distinct names and batch keys (the content fingerprint), land in
    separate batches with separately cached runners, and each come back
    with ITS OWN operator's solution — the failure mode being guarded:
    a runner cache keyed only on sizes would serve request 2 the
    compiled solve of request 1's matrix."""
    (d1, c1), (d2, c2) = _two_operators()
    b = jax.random.normal(jax.random.key(5), (d1.shape[0],), jnp.float32)
    p1, p2 = make(d1, c1, b), make(d2, c2, b)
    assert p1.name != p2.name
    assert p1.batch_key() != p2.batch_key()

    svc = SolverService(ServiceConfig(max_batch=8))
    rids = {svc.submit(p): p for p in (p1, p2)}
    results = svc.drain()
    assert svc.stats()["batches"] == 2
    assert len(svc.chosen_plans()) == 2
    for rid, prob in rids.items():
        got = jax.tree.leaves(results[rid].result)
        want = jax.tree.leaves(_single_result(prob, results[rid].plan))
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)
    # and the two answers genuinely differ (different operators)
    xs = [np.asarray(jax.tree.leaves(results[r].result)[0]) for r in rids]
    assert np.abs(xs[0] - xs[1]).max() > 1e-3
