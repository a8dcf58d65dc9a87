"""AsyncSolverService: continuous batching over persistent lane groups.

The engine's load-bearing contract (DESIGN.md §9): membership may churn —
requests admitted into free lanes at barriers mid-solve, converged lanes
retired individually — yet every request's result is bit-identical to
solving it alone under the same chunked device loop. Everything here is
deterministic: the clock is a fake tick counter and no test asserts
wall-clock durations.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec import BatchedProblem, CGProblem, Plan, StencilProblem, execute
from repro.kernels.common import get_spec
from repro.runtime.solver_service import (
    AsyncConfig,
    AsyncSolverService,
    ServiceOverloaded,
)
from repro.kernels.ops import load_dataset


def _tick_clock():
    ticks = itertools.count()
    return lambda: float(next(ticks))


def _stencil(seed, steps=10, shape=(32, 32)):
    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return StencilProblem(x, get_spec("2d5pt"), steps)


def _cg(data, cols, seed, iters=400, tol=1e-8):
    b = jax.random.normal(jax.random.key(seed), (data.shape[0],),
                          jnp.float32)
    return CGProblem.from_ell(data, cols, b, iters, tol=tol)


def _reference(problem, chunk):
    """The request solved alone under the engine's chunk cadence."""
    return execute(problem, Plan(tier="device_loop", sync_every=chunk))


def _sequential_stop_steps(problem, chunk):
    """Steps a lone chunked run executes before its check stops it."""
    from repro.core import perks

    check = problem.on_sync()
    steps = {"n": 0}

    def count(state, k):
        steps["n"] = k
        return check(state, k)

    perks.chunked_loop(problem.step_fn(), problem.n_steps,
                       sync_every=chunk, on_sync=count)(
        problem.initial_state())
    return steps["n"]


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


CHUNK = 5


@pytest.fixture(scope="module")
def poisson():
    return load_dataset("poisson_64")


def test_mixed_fleet_mid_solve_admission_bit_exact(poisson):
    """Mixed-key fleet, arrivals landing mid-solve: every result matches
    the request solved alone; groups never mix keys; the per-key compiled
    programs are reused across group activations."""
    data, cols = poisson
    eng = AsyncSolverService(AsyncConfig(max_batch=4, chunk_steps=CHUNK),
                             clock=_tick_clock())
    probs = {}
    for i in range(3):
        p = _cg(data, cols, i)
        probs[eng.submit(p)] = p
    for i in range(2):
        p = _stencil(100 + i)
        probs[eng.submit(p)] = p
    results = {}
    results.update(eng.step())               # two barriers of the CG group
    results.update(eng.step())
    late = _cg(data, cols, 50)               # arrives mid-solve
    probs[eng.submit(late)] = late
    results.update(eng.run_until_idle())

    assert set(results) == set(probs)
    for rid, p in probs.items():
        _assert_same(results[rid].result, _reference(p, CHUNK))
    stats = eng.stats()
    assert stats["served"] == 6
    assert stats["groups"] == 2              # one per key, never mixed
    assert stats["admitted_mid_solve"] >= 1
    assert stats["distinct_programs"] == 2
    assert 0.0 < stats["lane_occupancy"] <= 1.0
    # a later same-key burst reuses the cached programs (no new group
    # compile): the runner object identity is stable
    prog_ids = {k: id(p.runner) for k, p in eng._programs.items()}
    more = _cg(data, cols, 60)
    rid = eng.submit(more)
    out = eng.run_until_idle()
    _assert_same(out[rid].result, _reference(more, CHUNK))
    assert {k: id(p.runner) for k, p in eng._programs.items()} == prog_ids
    assert eng.stats()["groups"] == 3


def test_per_lane_early_retirement_matches_sequential_stop(poisson):
    """Each converged lane retires at exactly the barrier a lone chunked
    run would stop at — per-lane steps telemetry equals the sequential
    stop step, and results are bit-exact (never the static-batch
    behavior where the slowest instance owns every lane's step count)."""
    data, cols = poisson
    eng = AsyncSolverService(AsyncConfig(max_batch=4, chunk_steps=CHUNK),
                             clock=_tick_clock())
    probs = {eng.submit(p): p
             for p in (_cg(data, cols, 200 + i) for i in range(4))}
    results = eng.run_until_idle()
    for rid, p in probs.items():
        rr = results[rid]
        assert rr.steps == _sequential_stop_steps(p, CHUNK)
        assert rr.steps < p.n_steps          # genuinely early
        _assert_same(rr.result, _reference(p, CHUNK))
    assert eng.stats()["retired_early"] == 4


def test_partial_chunk_tail_is_masked_bit_exact():
    """n_steps not divisible by the chunk: the masked tail (full fused
    chunk, surplus steps discarded per lane) matches the sequential
    remainder dispatch bit-for-bit."""
    eng = AsyncSolverService(AsyncConfig(max_batch=2, chunk_steps=4),
                             clock=_tick_clock())
    p = _stencil(7, steps=10)                # 4 + 4 + masked tail of 2
    rid = eng.submit(p)
    out = eng.run_until_idle()
    _assert_same(out[rid].result, _reference(p, 4))
    assert out[rid].steps == 10


def test_backpressure_reject_and_shed():
    eng = AsyncSolverService(
        AsyncConfig(max_batch=2, max_queue=2, overload="reject"),
        clock=_tick_clock())
    eng.submit(_stencil(0))
    eng.submit(_stencil(1))
    with pytest.raises(ServiceOverloaded, match="queue full"):
        eng.submit(_stencil(2))
    assert eng.stats()["rejected"] == 1
    assert eng.pending() == 2

    shed = AsyncSolverService(
        AsyncConfig(max_batch=2, max_queue=2, overload="shed"),
        clock=_tick_clock())
    oldest = shed.submit(_stencil(0))
    kept = [shed.submit(_stencil(i)) for i in (1, 2)]
    out = shed.run_until_idle()
    assert oldest not in out and all(r in out for r in kept)
    assert shed.shed_ids() == [oldest]
    assert shed.stats()["shed"] == 1 and shed.stats()["served"] == 2


def test_sla_shed_drops_stale_requests_at_admission():
    """Under overload='shed' with a queue-wait SLA, a request whose wait
    already exceeds the SLA is dropped at admission instead of occupying
    a lane; under 'reject' it is served but counted as an SLA miss."""
    clock = _tick_clock()
    eng = AsyncSolverService(
        AsyncConfig(max_batch=1, chunk_steps=5, overload="shed",
                    sla_queued_s=30.0),
        clock=clock)
    stale = eng.submit(_stencil(0))
    for _ in range(40):                      # age it past the SLA
        clock()
    fresh = eng.submit(_stencil(1))
    out = eng.run_until_idle()
    assert fresh in out and stale not in out
    assert stale in eng.shed_ids()

    clock2 = _tick_clock()
    lax = AsyncSolverService(
        AsyncConfig(max_batch=1, chunk_steps=5, overload="reject",
                    sla_queued_s=30.0),
        clock=clock2)
    late = lax.submit(_stencil(0))
    for _ in range(40):
        clock2()
    out2 = lax.run_until_idle()
    assert late in out2
    assert lax.stats()["sla_misses"] >= 1


def test_seeded_arrival_trace_is_deterministic(poisson):
    """serve() under a seeded arrival trace: everything is served
    bit-exactly, and two fresh engines given the same trace agree on
    every scheduling counter (no wall-clock dependence with a fake
    clock + no-op sleep)."""
    data, cols = poisson
    rng = np.random.default_rng(42)
    offsets = np.cumsum(rng.exponential(40.0, size=8))
    mix = [_cg(data, cols, 300 + i) if i % 3 else _stencil(400 + i)
           for i in range(8)]
    trace = list(zip(offsets.tolist(), mix))

    def run_once():
        eng = AsyncSolverService(
            AsyncConfig(max_batch=4, chunk_steps=CHUNK),
            clock=_tick_clock())
        out = eng.serve(trace, sleep=lambda dt: None)
        return eng, out

    eng1, out1 = run_once()
    assert len(out1) == 8
    rid_by_order = sorted(out1)              # rids assigned in offset order
    for rid, p in zip(rid_by_order, mix):
        _assert_same(out1[rid].result, _reference(p, CHUNK))
        assert out1[rid].queued_s >= 0.0
        assert out1[rid].latency_s >= out1[rid].queued_s

    eng2, out2 = run_once()
    counters = ("served", "groups", "barriers", "admitted_mid_solve",
                "retired_early", "rejected", "shed", "sla_misses",
                "distinct_programs")
    s1, s2 = eng1.stats(), eng2.stats()
    assert {k: s1[k] for k in counters} == {k: s2[k] for k in counters}
    for k in ("p50_queued_s", "p99_queued_s", "p50_latency_s",
              "p99_latency_s", "p50_exec_s", "p99_exec_s"):
        assert s1[k] == s2[k] >= 0.0


def test_engine_rejects_prebatched_and_validates_config():
    eng = AsyncSolverService(clock=_tick_clock())
    bp = BatchedProblem.from_instances([_stencil(0)])
    with pytest.raises(TypeError, match="single-instance"):
        eng.submit(bp)
    with pytest.raises(ValueError, match="overload"):
        AsyncConfig(overload="panic")
    with pytest.raises(ValueError, match="max_batch"):
        AsyncConfig(max_batch=0)
    assert eng.step() == {}                  # idle engine is a no-op


def test_cold_activation_charges_plan_time_once(poisson):
    """The cold activation's planning cost lands on the requests admitted
    at activation (plan_s > 0); every later admission of the key reports
    exactly 0.0."""
    data, cols = poisson
    eng = AsyncSolverService(AsyncConfig(max_batch=2, chunk_steps=CHUNK),
                             clock=_tick_clock())
    cold = eng.submit(_cg(data, cols, 500))
    out = eng.run_until_idle()
    assert out[cold].plan_s > 0.0
    warm = eng.submit(_cg(data, cols, 501))
    out2 = eng.run_until_idle()
    assert out2[warm].plan_s == 0.0
