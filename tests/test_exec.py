"""The unified executor layer (repro.exec, DESIGN.md §7).

Covers the tentpole contracts:

* ``Plan`` is an immutable, JSON-round-trippable artifact;
* ``plan()`` is monotone: a larger VMEM budget never caches fewer
  bytes, a larger ``fuse_steps`` cap never costs more barriers;
* ``execute(problem, plan(problem))`` matches the jnp oracle over all 13
  stencil specs, and CG's device loop matches it over the full sparse
  registry;
* ``plan()``'s resident rows and CG policy are those of
  ``plan_resident_planes`` and ``cg_policy_from_arrays``;
* ``autotune`` measures the candidates and returns a member of them.
"""
import gc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import perks
from repro.core.hardware import CHIPS
from repro.exec import (
    BiCGStabProblem,
    CGProblem,
    CacheDecision,
    Plan,
    StencilProblem,
    autotune,
    execute,
    plan,
    plan_candidates,
)
from repro.core.cache_policy import cg_arrays
from repro.exec import adapters
from repro.exec.planner import cg_policy_from_arrays
from repro.kernels import ref
from repro.kernels.common import BENCHMARKS, get_spec
from repro.kernels.ops import load_dataset, load_sell
from repro.kernels.stencil3d import plan_resident_planes
from repro.sparse import REGISTRY

STEPS = 4


def _domain(spec):
    shape = (48, 64) if spec.ndim == 2 else (24, 16, 32)
    return jax.random.normal(jax.random.key(0), shape, jnp.float32)


# -- Plan: immutability + JSON round-trip ---------------------------------------

PLANS = [
    Plan(tier="host_loop", n_steps=7),
    Plan(tier="device_loop", sync_every=3, problem="cg_n64", chip="tpu_v5p"),
    Plan(tier="resident", cached_rows=48, sub_rows=16, fuse_steps=4,
         cache=(CacheDecision("domain_rows", 1024, 4096),),
         predicted_s=1.25e-3, predicted_bound="main_memory"),
    Plan(tier="resident", policy="MIX", block_rows=256,
         cache=(CacheDecision("r", 400, 400), CacheDecision("A", 100, 800))),
    Plan(tier="distributed", shard_axis="data", partition="nnz",
         fuse_reductions=True, inner_tier="host_loop"),
]


@pytest.mark.parametrize("p", PLANS, ids=lambda p: p.tier + str(p.fuse_steps))
def test_plan_json_round_trip(p):
    assert Plan.from_json(p.to_json()) == p
    # and via plain dicts (what a CI artifact reader would do)
    assert Plan.from_dict(p.to_dict()) == p


def test_plan_validation():
    with pytest.raises(ValueError):
        Plan(tier="warp_speed")
    with pytest.raises(ValueError):
        Plan(tier="resident", fuse_steps=0)
    with pytest.raises(ValueError):
        Plan(tier="distributed", partition="cols")
    with pytest.raises(ValueError):
        Plan.from_dict({"tier": "host_loop", "warp": 9})
    with pytest.raises(Exception):       # frozen
        p = Plan(tier="host_loop")
        p.tier = "resident"


def test_plan_derived_fields():
    p = Plan(tier="resident", n_steps=10, fuse_steps=4,
             cache=(CacheDecision("a", 10, 40), CacheDecision("b", 5, 5)))
    assert p.barriers == 3
    assert p.cached_bytes == 15
    assert p.cache[0].fraction == 0.25


# -- planner: candidates, monotonicity, the row and policy choices ---------------------

def test_plan_candidates_ranked_and_typed():
    spec = get_spec("2d5pt")
    problem = StencilProblem(_domain(spec), spec, STEPS)
    cands = plan_candidates(problem)
    assert len(cands) >= 3
    preds = [c.predicted_s for c in cands]
    assert preds == sorted(preds)
    assert {c.tier for c in cands} >= {"host_loop", "device_loop", "resident"}
    assert all(c.n_steps == STEPS for c in cands)
    # planning needs shapes only — a ShapeDtypeStruct domain works
    big = StencilProblem(
        jax.ShapeDtypeStruct((8192, 8192), jnp.float32), spec, 1000)
    assert plan(big).tier == "resident"


def test_planner_vmem_budget_monotonicity():
    """Larger VMEM budget => the chosen plan never caches fewer bytes."""
    spec = get_spec("2d9pt")
    problem = StencilProblem(
        jax.ShapeDtypeStruct((4096, 2048), jnp.float32), spec, 100)
    prev = -1
    for budget in (1 << 20, 4 << 20, 16 << 20, 64 << 20, 256 << 20,
                   1 << 30):
        chosen = plan(problem, budget_bytes=budget)
        assert chosen.cached_bytes >= prev, (budget, chosen)
        prev = chosen.cached_bytes
    assert prev > 0   # the sweep must actually reach the caching regime


def test_planner_fuse_cap_monotonicity():
    """Larger fuse_steps cap => the chosen plan never pays more barriers."""
    spec = get_spec("2d5pt")
    problem = StencilProblem(
        jax.ShapeDtypeStruct((4096, 2048), jnp.float32), spec, 64)
    prev = None
    for cap in (1, 2, 4, 8, 16):
        chosen = plan(problem, max_fuse=cap)
        if prev is not None:
            assert chosen.barriers <= prev, (cap, chosen)
        prev = chosen.barriers


def test_planner_chip_capacity_sensitivity():
    """A chip with less on-chip memory can never cache more (same problem).

    Asserted over the *candidate set* (its max cached bytes), not the
    ranked winner: since the deep schedule axis (DESIGN.md §12) the
    winner may deliberately trade resident rows for wavefront scratch —
    a bigger-VMEM chip can pick a deeper, less-cached plan because it is
    faster, so only the capacity frontier is monotone."""
    spec = get_spec("2d5pt")
    problem = StencilProblem(
        jax.ShapeDtypeStruct((4096, 2048), jnp.float32), spec, 100)
    by_cap = sorted(("a100", "v100", "tpu_v5e"),
                    key=lambda n: CHIPS[n].onchip_bytes)
    cached = [max(c.cached_bytes for c in plan_candidates(problem, chip=n)
                  if c.tier == "resident")
              for n in by_cap]
    assert cached == sorted(cached)
    assert cached[-1] > 0


def test_plan_subsumes_legacy_stencil_planner():
    """plan() resident candidates carry exactly plan_resident_planes' rows."""
    spec = get_spec("2d5pt")
    problem = StencilProblem(
        jax.ShapeDtypeStruct((4096, 4096), jnp.float32), spec, 1000)
    rows = plan_resident_planes((4096, 4096), 4, spec, chip=CHIPS["tpu_v5e"])
    cands = plan_candidates(problem)
    resident_t1 = next(c for c in cands
                       if c.tier == "resident" and c.fuse_steps == 1)
    assert resident_t1.cached_rows == rows
    assert resident_t1.cache[0].cached_bytes == rows * 4096 * 4


def _cg_policy(n, nnz):
    budget = int(CHIPS["tpu_v5e"].onchip_bytes * 0.9)
    return cg_policy_from_arrays(cg_arrays(n, nnz, 4), budget)["policy"]


def test_plan_subsumes_legacy_cg_planner():
    """The CG candidates' policy agrees with cg_policy_from_arrays."""
    for n, nnz in ((10_000, 50_000), (10**6, 3 * 10**8)):
        policy = _cg_policy(n, nnz)
        b = jax.ShapeDtypeStruct((n,), jnp.float32)
        problem = CGProblem(b=b, n_steps=8,
                            data=jax.ShapeDtypeStruct((n, max(1, nnz // n)),
                                                      jnp.float32),
                            cols=None)
        cands = plan_candidates(problem)
        if policy == "IMP":
            assert all(c.tier != "resident" for c in cands)
        else:
            assert any(c.policy == policy for c in cands)
    # huge problem: vectors alone exceed VMEM -> IMP == no resident cand
    assert _cg_policy(10**9, 10**10) == "IMP"


# -- the planner's own plan against the oracle: all 13 stencil specs -----------

@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_executor_matches_legacy_stencil(name):
    """execute() under the plan the planner picks matches the jnp oracle
    on every spec, whatever tier that is."""
    spec = get_spec(name)
    x = _domain(spec)
    problem = StencilProblem(x, spec, STEPS)
    np.testing.assert_allclose(
        np.asarray(execute(problem, plan(problem))),
        np.asarray(ref.stencil_run(x, spec, STEPS)), rtol=0, atol=1e-5)


# -- CG's device loop against the oracle: the full sparse registry -------------

@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_executor_matches_legacy_cg(name):
    """Exact where CG keeps the ELL gather the oracle runs; to f32
    reassociation where the DIA SpMV sums in another order."""
    data, cols = load_dataset(name)
    b = jax.random.normal(jax.random.key(1), (data.shape[0],), jnp.float32)
    iters = 5
    problem = CGProblem.from_ell(data, cols, b, iters)
    x_new, rr_new = execute(problem, Plan(tier="device_loop"))
    x_ref, rr_ref = ref.cg_run(data, cols, b, iters)
    if problem.spmv_format == "ell":
        np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_ref))
        assert float(rr_new) == float(rr_ref)
    else:
        np.testing.assert_allclose(np.asarray(x_new), np.asarray(x_ref),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(rr_new), float(rr_ref), rtol=1e-5)


def test_executor_matches_legacy_cg_fused_and_sell():
    """Resident MIX and the SELL matvec against the device loop."""
    data, cols = load_dataset("poisson_64")
    b = jax.random.normal(jax.random.key(1), (data.shape[0],), jnp.float32)
    p = CGProblem.from_ell(data, cols, b, 8)
    x_res, rr_res = execute(p, Plan(tier="resident", policy="MIX",
                                    block_rows=256))
    x_loop, rr_loop = execute(p, Plan(tier="device_loop"))
    np.testing.assert_allclose(np.asarray(x_res), np.asarray(x_loop),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(rr_res), float(rr_loop), rtol=1e-4)
    op = load_sell("graph_powerlaw_8k")
    bs = jax.random.normal(jax.random.key(2), (op.n_rows,), jnp.float32)
    ds, cs = load_dataset("graph_powerlaw_8k")
    x_sell, _ = execute(CGProblem.from_matvec(op.matvec, bs, 5,
                                              matrix=op.matrix),
                        Plan(tier="device_loop"))
    x_ell, _ = execute(CGProblem.from_ell(ds, cs, bs, 5),
                       Plan(tier="device_loop"))
    np.testing.assert_allclose(np.asarray(x_sell), np.asarray(x_ell),
                               rtol=1e-5, atol=1e-5)


def test_executor_early_stop_matches_legacy():
    """The device loop's early stop, synced every 25 steps, lands where
    the host loop checking at the same cadence stops: the same iterate,
    bit for bit, well before the step cap."""
    data, cols = load_dataset("poisson_64")
    b = jax.random.normal(jax.random.key(0), (data.shape[0],), jnp.float32)
    p = CGProblem.from_ell(data, cols, b, 500, tol=1e-10)
    reg = obs.MetricsRegistry()
    with obs.use_metrics(reg):
        x_dev, rr_dev = execute(p, Plan(tier="device_loop", sync_every=25))
    assert reg.value("executor_steps_total", tier="device_loop") < 500
    x_host, rr_host = execute(p, Plan(tier="host_loop", fuse_steps=25))
    assert float(rr_dev) < 1e-10 * float(jnp.vdot(b, b)) * 10
    np.testing.assert_array_equal(np.asarray(x_dev), np.asarray(x_host))
    assert float(rr_dev) == float(rr_host)


def test_declared_convergence_check_is_planned_and_honored():
    """A problem that declares tol gets host-sync points from the planner
    (device-loop candidates carry sync_every) and early-stops; a
    hand-built plan that drops the check warns instead of silently
    running all steps."""
    data, cols = load_dataset("poisson_64")
    b = jax.random.normal(jax.random.key(0), (data.shape[0],), jnp.float32)
    problem = CGProblem.from_ell(data, cols, b, 500, tol=1e-10)
    dev = next(c for c in plan_candidates(problem)
               if c.tier == "device_loop")
    assert dev.sync_every is not None and dev.sync_every < 500
    x, rr = execute(problem, dev)
    assert float(rr) < 1e-10 * float(jnp.vdot(b, b)) * 10
    with pytest.warns(RuntimeWarning, match="convergence check"):
        execute(problem, Plan(tier="device_loop"))   # check dropped


def test_host_loop_honors_declared_convergence():
    """The baseline tier syncs every step, so a tol-declaring CG problem
    early-stops there WITHOUT a drop-warning, and matches the manual
    per-step loop with the same check bit-for-bit."""
    from repro.exec.executor import honors_on_sync

    data, cols = load_dataset("poisson_64")
    b = jax.random.normal(jax.random.key(7), (data.shape[0],), jnp.float32)
    problem = CGProblem.from_ell(data, cols, b, 500, tol=1e-10)
    assert honors_on_sync(Plan(tier="host_loop"), 500)
    assert honors_on_sync(Plan(tier="host_loop", fuse_steps=4), 500)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        x, rr = execute(problem, Plan(tier="host_loop"))
    # reference: the same step/check cadence, hand-rolled
    step = jax.jit(problem.step_fn())
    check = problem.on_sync()
    state = problem.initial_state()
    for k in range(500):
        state = step(state)
        if check(state, k + 1):
            break
    assert k + 1 < 500                       # it really stopped early
    np.testing.assert_array_equal(np.asarray(x), np.asarray(state[0]))
    assert float(rr) == float(state[3])


def test_prediction_ratio_none_vs_zero():
    """predicted_s=None means NO prediction (ratio None); predicted_s=0.0
    is a real prediction and must not be swallowed by a falsy check."""
    import math

    from repro.exec.executor import TimingRow

    p = Plan(tier="host_loop")
    assert TimingRow(p, None, 0.5).prediction_ratio is None
    assert TimingRow(p, 0.0, 0.5).prediction_ratio == math.inf
    assert TimingRow(p, 0.0, 0.0).prediction_ratio == 1.0
    assert TimingRow(p, 0.25, 0.5).prediction_ratio == pytest.approx(2.0)


def test_executor_rejects_mismatched_plan():
    spec = get_spec("2d5pt")
    x = _domain(spec)
    problem = StencilProblem(x, spec, STEPS)
    with pytest.raises(ValueError):
        execute(problem, Plan(tier="device_loop", n_steps=STEPS + 1))
    with pytest.raises(ValueError):
        execute(problem, Plan(tier="distributed"))       # no mesh
    with pytest.raises(NotImplementedError):
        # matvec-only CG has no fused-kernel tier
        p = CGProblem.from_matvec(lambda v: v, x[:, 0], 3)
        execute(p, Plan(tier="resident", policy="MIX"))


# -- autotune -------------------------------------------------------------------

def test_autotune_returns_measured_winner():
    spec = get_spec("2d5pt")
    problem = StencilProblem(_domain(spec), spec, STEPS)
    res = autotune(problem, top_k=3, warmup=0, iters=1)
    assert res.best in [r.plan for r in res.table]
    assert all(r.measured_s > 0 for r in res.table)
    assert res.best == min(res.table, key=lambda r: r.measured_s).plan
    # the table preserves the planner's predicted order
    preds = [r.predicted_s for r in res.table]
    assert preds == sorted(preds)
    # every plan in the table round-trips through JSON (loggable artifact)
    for r in res.table:
        assert Plan.from_json(r.plan.to_json()) == r.plan


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", "tpu_v5e"),
    ("tpu", "TPU v99", None),          # unknown kind: an error
    ("gpu", "NVIDIA A100", None),      # no kernels for a GPU: an error
    ("cpu", "cpu", "tpu_v5e"),         # tests plan for the v5e, interpreted
])
def test_attached_chip_resolves_device_kind(platform, kind, want,
                                            monkeypatch):
    from types import SimpleNamespace
    from repro.core.hardware import attached_chip
    limit = 16_911_433_728          # 15.75 GiB, a v5e's usable HBM
    dev = SimpleNamespace(platform=platform, device_kind=kind,
                          memory_stats=lambda: {"bytes_limit": limit})
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    if want is None:
        with pytest.raises(ValueError, match=kind if platform == "tpu"
                           else repr(platform)):
            attached_chip()
    else:
        chip = attached_chip()
        assert chip.name == want
        assert chip.interpret == (platform == "cpu")
        # the device's usable HBM on a chip; the published figure planned
        # for on the CPU
        assert chip.hbm_bytes == (limit if platform == "tpu" else 16 * 2**30)


# -- the cached loop runner ----------------------------------------------------

RUNNER_CASES = {
    "cg-dia": (CGProblem, "poisson_64", "dia"),
    "cg-ell": (CGProblem, "graph_regular_4k", "ell"),
    "bicgstab": (BiCGStabProblem, "poisson_64", None),
}
SYNCED = Plan(tier="device_loop", sync_every=20)


def _krylov(cls, data, cols, seed):
    """60 iterations, synced every 20 against a tolerance never met."""
    b = jax.random.normal(jax.random.key(seed), (data.shape[0],),
                          jnp.float32)
    return cls.from_ell(data, cols, b, 60, tol=1e-30)


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("case", sorted(RUNNER_CASES))
def test_second_solve_over_one_operator_reuses_the_runner(case,
                                                          fresh_runners):
    """Two solves over one operator with different right-hand sides: the
    runner is built once, the second solve dispatches it with no
    ``repro.compile``, and its answer is bit for bit a cold run's."""
    cls, name, spmv = RUNNER_CASES[case]
    data, cols = load_dataset(name)
    first = _krylov(cls, data, cols, 0)
    if cls is CGProblem:
        assert first.spmv_format == spmv
    reg, tr = obs.MetricsRegistry(), obs.Tracer()
    with obs.use_metrics(reg):
        execute(first, SYNCED)
        with obs.use_tracer(tr):
            warm = execute(_krylov(cls, data, cols, 1), SYNCED)
    assert reg.value("executor_retraces_total", tier="device_loop") == 1
    assert reg.value("executor_runner_hits_total", tier="device_loop") == 1
    assert tr.by_cat("compile") == []
    assert [dict(e.args)["steps"] for e in tr.by_cat("chunk")] == [20] * 3

    perks._RUNNERS.clear()
    cold_reg = obs.MetricsRegistry()
    with obs.use_metrics(cold_reg):
        cold = execute(_krylov(cls, data, cols, 1), SYNCED)
    assert cold_reg.value("executor_retraces_total", tier="device_loop") == 1
    _assert_same(warm, cold)


def test_a_same_shaped_operator_gets_its_own_answer(fresh_runners):
    """Another operator of the same structure reuses the executable, and
    its values, passed as arguments, give its own answer: 2A x = b is
    solved by half of A's x, bit for bit as a cold run solves it."""
    data, cols = load_dataset("poisson_64")
    data2 = data * 2.0
    b = jax.random.normal(jax.random.key(3), (data.shape[0],), jnp.float32)
    reg = obs.MetricsRegistry()
    with obs.use_metrics(reg):
        x1, _ = execute(CGProblem.from_ell(data, cols, b, 60, tol=1e-30),
                        SYNCED)
        x2, _ = execute(CGProblem.from_ell(data2, cols, b, 60, tol=1e-30),
                        SYNCED)
    assert reg.value("executor_retraces_total", tier="device_loop") == 1
    assert reg.value("executor_runner_hits_total", tier="device_loop") == 1
    np.testing.assert_allclose(np.asarray(x1), 2 * np.asarray(x2),
                               rtol=1e-6, atol=0)
    perks._RUNNERS.clear()
    cold, _ = execute(CGProblem.from_ell(data2, cols, b, 60, tol=1e-30),
                      SYNCED)
    _assert_same(x2, cold)


def test_the_operator_outlives_its_solves(fresh_runners):
    """The runners donate the state only: the ELL planes and the DIA
    planes they were converted to are readable after two solves each."""
    data, cols = load_dataset("poisson_64")
    for cls in (CGProblem, BiCGStabProblem):
        for seed in (0, 1):
            execute(_krylov(cls, data, cols, seed), SYNCED)
    _, planes = adapters.dia_operator(data, cols)
    for a in (data, cols, *planes):
        assert not a.is_deleted()
        assert np.isfinite(np.asarray(a)).all()


def test_fresh_closures_run_and_the_cache_stays_bounded(fresh_runners):
    """A caller passing a new closure every call gets a runner of its own
    each time; the cache keeps at most ``_RUNNERS_SIZE`` of them, and none
    once the closures are gone."""
    kept = []
    for k in range(perks._RUNNERS_SIZE + 4):
        kept.append(lambda s, k=k: s + k)
        out = perks.device_loop(kept[-1], 3)(jnp.zeros(4, jnp.int32))
        np.testing.assert_array_equal(np.asarray(out), np.full(4, 3 * k))
    assert len(perks._RUNNERS) == perks._RUNNERS_SIZE
    del kept
    gc.collect()
    assert len(perks._RUNNERS) == 0
