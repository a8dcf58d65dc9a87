"""The unified executor layer (repro.exec, DESIGN.md §7).

Covers the tentpole contracts:

* ``Plan`` is an immutable, JSON-round-trippable artifact;
* ``plan()`` is monotone: a larger VMEM budget never caches fewer
  bytes, a larger ``fuse_steps`` cap never costs more barriers;
* ``execute(problem, plan)`` reproduces every legacy ``run_*`` result
  bit-identically over all 13 stencil specs and the full sparse
  registry (fuse_steps > 1 included — same code, same compiled graph);
* ``plan()`` subsumes the legacy planner entry points (``plan_for``,
  ``plan_policy`` agree with the Plan the planner emits);
* every legacy ``run_*`` shim warns exactly once per entry point;
* ``autotune`` measures the candidates and returns a member of them.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hardware import CHIPS
from repro.exec import (
    CGProblem,
    CacheDecision,
    Plan,
    StencilProblem,
    autotune,
    execute,
    plan,
    plan_candidates,
)
from repro.exec.deprecation import reset_warnings
from repro.kernels.common import BENCHMARKS, get_spec
from repro.solvers import cg as cgs
from repro.solvers import stencil as ssol
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


# -- planner: candidates, monotonicity, legacy subsumption ----------------------

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
    """plan() resident candidates carry exactly plan_for's row decision."""
    spec = get_spec("2d5pt")
    problem = StencilProblem(
        jax.ShapeDtypeStruct((4096, 4096), jnp.float32), spec, 1000)
    legacy = ssol.plan_for((4096, 4096), 4, spec)
    cands = plan_candidates(problem)
    resident_t1 = next(c for c in cands
                       if c.tier == "resident" and c.fuse_steps == 1)
    assert resident_t1.cached_rows == legacy["cached_rows"]
    assert resident_t1.cache[0].cached_bytes == legacy["cached_cells"] * 4


def test_plan_subsumes_legacy_cg_planner():
    """The CG candidates' policy agrees with legacy plan_policy."""
    for n, nnz in ((10_000, 50_000), (10**6, 3 * 10**8)):
        legacy = cgs.plan_policy(n, nnz)
        b = jax.ShapeDtypeStruct((n,), jnp.float32)
        problem = CGProblem(b=b, n_steps=8,
                            data=jax.ShapeDtypeStruct((n, max(1, nnz // n)),
                                                      jnp.float32),
                            cols=None)
        cands = plan_candidates(problem)
        if legacy["policy"] == "IMP":
            assert all(c.tier != "resident" for c in cands)
        else:
            assert any(c.policy == legacy["policy"] for c in cands)
    # huge problem: vectors alone exceed VMEM -> IMP == no resident cand
    assert cgs.plan_policy(10**9, 10**10)["policy"] == "IMP"


# -- executor vs legacy: all 13 stencil specs -----------------------------------

@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_executor_matches_legacy_stencil(name):
    """execute() must reproduce every legacy run_* bit-identically (the
    shims route through the same code; this guards the routing)."""
    spec = get_spec(name)
    x = _domain(spec)
    problem = StencilProblem(x, spec, STEPS)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy_host = ssol.run_host_loop(x, spec, STEPS)
        legacy_dev = ssol.run_device_loop(x, spec, STEPS)
        legacy_res = ssol.run_resident(x, spec, STEPS,
                                       cached_rows=x.shape[0] // 2,
                                       sub_rows=8)
        legacy_fused = ssol.run_resident(x, spec, STEPS,
                                         cached_rows=x.shape[0] // 2,
                                         sub_rows=32, fuse_steps=2)
    np.testing.assert_array_equal(
        np.asarray(execute(problem, Plan(tier="host_loop"))),
        np.asarray(legacy_host))
    np.testing.assert_array_equal(
        np.asarray(execute(problem, Plan(tier="device_loop"))),
        np.asarray(legacy_dev))
    np.testing.assert_array_equal(
        np.asarray(execute(problem, Plan(tier="resident",
                                         cached_rows=x.shape[0] // 2,
                                         sub_rows=8))),
        np.asarray(legacy_res))
    # fuse_steps > 1: same plan -> same compiled graph -> still exact
    np.testing.assert_array_equal(
        np.asarray(execute(problem, Plan(tier="resident",
                                         cached_rows=x.shape[0] // 2,
                                         sub_rows=32, fuse_steps=2))),
        np.asarray(legacy_fused))


# -- executor vs legacy: the full sparse registry -------------------------------

@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_executor_matches_legacy_cg(name):
    data, cols = cgs.load_dataset(name)
    b = jax.random.normal(jax.random.key(1), (data.shape[0],), jnp.float32)
    iters = 5
    problem = CGProblem.from_ell(data, cols, b, iters)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        x_leg, rr_leg = cgs.run_device_loop(data, cols, b, iters)
    x_new, rr_new = execute(problem, Plan(tier="device_loop"))
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_leg))
    assert float(rr_new) == float(rr_leg)


def test_executor_matches_legacy_cg_fused_and_sell():
    data, cols = cgs.load_dataset("poisson_64")
    b = jax.random.normal(jax.random.key(1), (data.shape[0],), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        x_leg, rr_leg = cgs.run_fused(data, cols, b, 8, policy="MIX")
        op = cgs.load_sell("graph_powerlaw_8k")
        bs = jax.random.normal(jax.random.key(2), (op.n_rows,), jnp.float32)
        x_sell_leg, _ = cgs.run_device_loop_sell(op, bs, 5)
    p = CGProblem.from_ell(data, cols, b, 8)
    x_new, rr_new = execute(p, Plan(tier="resident", policy="MIX",
                                    block_rows=256))
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_leg))
    ps = CGProblem.from_matvec(op.matvec, bs, 5)
    x_sell_new, _ = execute(ps, Plan(tier="device_loop"))
    np.testing.assert_array_equal(np.asarray(x_sell_new),
                                  np.asarray(x_sell_leg))


def test_executor_early_stop_matches_legacy():
    data, cols = cgs.load_dataset("poisson_64")
    b = jax.random.normal(jax.random.key(0), (data.shape[0],), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        x_leg, rr_leg = cgs.run_device_loop(data, cols, b, 500,
                                            sync_every=25, tol=1e-10)
    p = CGProblem.from_ell(data, cols, b, 500, tol=1e-10)
    x_new, rr_new = execute(p, Plan(tier="device_loop", sync_every=25))
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_leg))
    assert float(rr_new) == float(rr_leg)


def test_declared_convergence_check_is_planned_and_honored():
    """A problem that declares tol gets host-sync points from the planner
    (device-loop candidates carry sync_every) and early-stops; a
    hand-built plan that drops the check warns instead of silently
    running all steps."""
    data, cols = cgs.load_dataset("poisson_64")
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

    data, cols = cgs.load_dataset("poisson_64")
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


# -- deprecation hygiene --------------------------------------------------------

STENCIL_SHIMS = ("run_host_loop", "run_device_loop", "run_resident",
                 "run_distributed")
CG_SHIMS = ("run_host_loop", "run_device_loop", "run_device_loop_sell",
            "run_fused", "run_distributed")


def _call_shim(module, entry):
    spec = get_spec("2d5pt")
    x = jax.random.normal(jax.random.key(0), (16, 16), jnp.float32)
    if module is ssol:
        if entry == "run_distributed":
            # needs a mesh; validation raises before any warning matters —
            # exercise the warn path via a 1-chip mesh if available
            from repro.dist.mesh import make_mesh
            mesh = make_mesh((1,), ("data",))
            return ssol.run_distributed(x, spec, 2, mesh)
        return getattr(ssol, entry)(x, spec, 2)
    data, cols = cgs.load_dataset("poisson_64")
    b = jnp.ones((data.shape[0],), jnp.float32)
    if entry == "run_device_loop_sell":
        op = cgs.load_sell("poisson_64")
        return cgs.run_device_loop_sell(op, b, 2)
    if entry == "run_fused":
        return cgs.run_fused(data, cols, b, 2)
    if entry == "run_distributed":
        from repro.dist.mesh import make_mesh
        mesh = make_mesh((1,), ("data",))
        return cgs.run_distributed(data, cols, b, 2, mesh)
    return getattr(cgs, entry)(data, cols, b, 2)


@pytest.mark.parametrize("module,entry",
                         [(ssol, e) for e in STENCIL_SHIMS]
                         + [(cgs, e) for e in CG_SHIMS],
                         ids=lambda v: v if isinstance(v, str) else
                         v.__name__.rsplit(".", 1)[-1])
def test_legacy_shim_warns_exactly_once(module, entry):
    reset_warnings()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _call_shim(module, entry)
        first = [x for x in w if issubclass(x.category, DeprecationWarning)
                 and entry in str(x.message)]
        assert len(first) == 1, [str(x.message) for x in w]
        assert "repro.exec" in str(first[0].message)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _call_shim(module, entry)     # second call: silent
        again = [x for x in w if issubclass(x.category, DeprecationWarning)
                 and entry in str(x.message)]
        assert again == [], [str(x.message) for x in w]
    reset_warnings()


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
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    if want is None:
        with pytest.raises(ValueError, match=kind if platform == "tpu"
                           else repr(platform)):
            attached_chip()
    else:
        chip = attached_chip()
        assert chip.name == want
        assert chip.interpret == (platform == "cpu")
