"""Deep temporal blocking (DESIGN.md §12): wavefront kernel + planner axis.

The deep schedule (``kernels.stencil2d.stencil_perks_deep``) advances t
time steps per HBM streaming pass on a wavefront over VMEM scratch tiles
— every uncached row read and written exactly once per pass, edge halos
carried in stashes instead of the shallow schedule's ``radius*t``-wide
redundant recompute. This module pins, per ISSUE/DESIGN.md §12:

  * deep == loop-tier arithmetic over the WHOLE stencil zoo (all 13
    specs), including non-dividing block tails and ``n_steps % t != 0``;
  * the traffic model ``gm_bytes_deep`` is monotone non-increasing in t
    at fixed cache (the entire point of depth), property-tested;
  * the planner never emits a deep candidate whose scratch working set
    exceeds the chip's VMEM budget, and its deep pick beats every
    shallow fuse<=4 resident candidate on projected HBM traffic for the
    2D quick-bench specs;
  * ``Plan.validate()`` rejects infeasible resident geometry with a
    message naming the violated constraint (the executor-level home of
    what used to be a bare kernel assert);
  * deep plans run under ``BatchedProblem`` at B in {1, 8} bit-matching
    the per-instance runs;
  * the adapter's structural chunk/dma trace events reproduce the
    traffic model exactly (summed streamed bytes + 2*cached == model).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hyp import given, settings, st

from repro import obs
from repro.core.cache_policy import (
    gm_bytes_deep,
    gm_bytes_fused,
)
from repro.core.hardware import TPU_V5E
from repro.exec import Plan, StencilProblem, execute, plan_candidates
from repro.exec.batch import BatchedProblem, per_instance_chip
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.common import BENCHMARKS, get_spec
from repro.kernels.stencil2d import deep_vmem_bytes
from repro.obs.trace import Tracer


def _domain(spec, seed=0):
    shape = (48, 64) if spec.ndim == 2 else (24, 16, 32)
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def _loop(x, spec, steps):
    for _ in range(steps):
        x = ref.stencil_step(x, spec=spec)
    return x


# -- kernel equivalence over the whole zoo ------------------------------------

@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_deep_matches_loop_all_specs(name):
    """Deep wavefront == per-step loop for every spec: partial residency,
    t=4 over 11 steps (non-dividing remainder pass of 3), block size that
    does not divide the streamed region."""
    spec = get_spec(name)
    x = _domain(spec)
    steps, t = 11, 4
    cached = max(spec.radius, (x.shape[0] // 3) & ~7)  # partial, ragged
    got = kops.stencil_perks_deep(x, spec=spec, steps=steps,
                                  cached_rows=cached, sub_rows=8,
                                  fuse_steps=t)
    want = _loop(x, spec, steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=0)


@pytest.mark.parametrize("cached_rows", [0, 8, 24, 48])
@pytest.mark.parametrize("steps,t", [(1, 8), (7, 8), (8, 8), (16, 8),
                                     (5, 2), (9, 16)])
def test_deep_tails_and_residency_sweep(cached_rows, steps, t):
    """n_steps % t != 0 (remainder wave), t > n_steps (clamped), zero and
    full residency, tail blocks narrower than sub_rows."""
    spec = get_spec("2d9pt")
    x = _domain(spec)
    got = kops.stencil_perks_deep(x, spec=spec, steps=steps,
                                  cached_rows=cached_rows, sub_rows=9,
                                  fuse_steps=t)
    want = _loop(x, spec, steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6, rtol=0)


def test_deep_executes_from_planner_plan():
    """End-to-end: the planner's own deep candidate runs through execute()
    and matches the oracle."""
    spec = get_spec("2d5pt")
    x = _domain(spec)
    problem = StencilProblem(x, spec, 11)
    deep = [c for c in plan_candidates(problem, max_fuse=4)
            if c.schedule == "deep"]
    assert deep, "planner emitted no deep candidates"
    got = execute(problem, deep[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(problem.oracle()),
                               atol=1e-5, rtol=0)


# -- batched execution ---------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 8])
def test_deep_under_batched_problem(batch):
    spec = get_spec("2d5pt")
    instances = [StencilProblem(_domain(spec, seed=i), spec, 6)
                 for i in range(batch)]
    bp = BatchedProblem(instances)
    plan = Plan(tier="resident", schedule="deep", fuse_steps=4,
                cached_rows=16, sub_rows=8, batch=batch, n_steps=6)
    out = execute(bp, plan)
    for inst, got in zip(instances, bp.split(out)):
        alone = execute(inst, Plan(tier="resident", schedule="deep",
                                   fuse_steps=4, cached_rows=16, sub_rows=8,
                                   n_steps=6))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))


def test_per_instance_chip_scales_budget():
    assert per_instance_chip(TPU_V5E, 1) is TPU_V5E
    half = per_instance_chip(TPU_V5E, 2)
    assert half.onchip_bytes == TPU_V5E.onchip_bytes / 2
    assert half.hbm_bw == TPU_V5E.hbm_bw


# -- traffic model -------------------------------------------------------------

@given(t_small=st.integers(1, 64), delta=st.integers(1, 64),
       n_steps=st.integers(1, 500), cached_frac=st.floats(0.0, 1.0))
@settings(max_examples=60, deadline=None)
def test_gm_bytes_deep_monotone_in_t(t_small, delta, n_steps, cached_frac):
    """More depth never costs more traffic at fixed cache — deep has no
    overlap term, so A_gm = ceil(N/t)*2*uncached + 2*cached can only fall
    (or stay, when the pass count ties) as t grows."""
    domain = 1 << 20
    cached = int(domain * cached_frac)
    lo = gm_bytes_deep(n_steps, domain, cached, fuse_steps=t_small + delta)
    hi = gm_bytes_deep(n_steps, domain, cached, fuse_steps=t_small)
    assert lo <= hi


def test_gm_bytes_deep_beats_fused_at_equal_depth():
    """At the same (t, cache) the deep model never exceeds the shallow
    model: it is the shallow traffic minus the per-pass overlap re-read."""
    domain, cached, rb, r = 1 << 20, 1 << 18, 1 << 10, 2
    for t in (1, 2, 4, 8):
        deep = gm_bytes_deep(100, domain, cached, fuse_steps=t)
        shallow = gm_bytes_fused(100, domain, cached, row_bytes=rb,
                                 radius=r, fuse_steps=t)
        assert deep <= shallow


# -- planner contract ----------------------------------------------------------

def _quick_2d_problems():
    # (8192, 8192) f32 = 256 MB: larger than VMEM, so residency is partial
    # and the schedules differ in streamed traffic (the Fig. 5 regime)
    for name in ("2d5pt", "2d9pt", "2ds25pt"):
        spec = get_spec(name)
        x = jax.ShapeDtypeStruct((8192, 8192), jnp.float32)
        yield name, StencilProblem(x, spec, 1000)


@pytest.mark.parametrize("batch", [1, 8])
def test_planner_deep_scratch_fits_vmem(batch):
    """The planner must never emit a deep candidate whose whole VMEM
    footprint (resident rows, wavefront scratch, temporaries: the kernel's
    scoped-VMEM request) exceeds the per-instance VMEM."""
    for name, problem in _quick_2d_problems():
        chip = per_instance_chip(TPU_V5E, batch)
        for c in plan_candidates(problem, batch=batch):
            if c.schedule != "deep":
                continue
            need = deep_vmem_bytes(problem.x.shape, problem.x.dtype,
                                   problem.spec, cached_rows=c.cached_rows,
                                   sub_rows=c.sub_rows,
                                   fuse_steps=c.fuse_steps)
            assert need <= chip.onchip_bytes, (name, c.fuse_steps)


def test_planner_deep_beats_shallow_traffic_2d():
    """For every 2D quick-bench spec the best deep candidate's projected
    HBM traffic undercuts every shallow fuse<=4 resident candidate."""
    for name, problem in _quick_2d_problems():
        cands = plan_candidates(problem, max_fuse=4)
        res = [c for c in cands if c.tier == "resident"]
        row_bytes = 8192 * 4
        dom = 8192 * row_bytes

        def traffic(c):
            cached = (c.cached_rows or 0) * row_bytes
            if c.schedule == "deep":
                return gm_bytes_deep(c.n_steps, dom, cached,
                                     fuse_steps=c.fuse_steps)
            return gm_bytes_fused(c.n_steps, dom, cached,
                                  row_bytes=row_bytes,
                                  radius=problem.spec.radius,
                                  fuse_steps=c.fuse_steps)

        deep = [traffic(c) for c in res if c.schedule == "deep"]
        shallow = [traffic(c) for c in res if c.schedule == "shallow"]
        assert deep and shallow, name
        assert min(deep) < min(shallow), name


def test_planner_unclamps_depth_for_deep():
    """max_fuse=4 caps shallow candidates, but deep depth is enumerated
    past it (up to DEEP_MAX_FUSE) when the scratch fits."""
    from repro.exec.planner import DEEP_MAX_FUSE
    assert DEEP_MAX_FUSE > 4
    _, problem = next(iter(_quick_2d_problems()))
    cands = plan_candidates(problem, max_fuse=4)
    deep_ts = {c.fuse_steps for c in cands if c.schedule == "deep"}
    shallow_ts = {c.fuse_steps for c in cands
                  if c.tier == "resident" and c.schedule == "shallow"}
    assert max(shallow_ts) <= 4
    assert max(deep_ts) > 4


# -- Plan.validate -------------------------------------------------------------

def test_validate_rejects_shallow_narrow_subtile():
    p = Plan(tier="resident", fuse_steps=8, cached_rows=8, sub_rows=4,
             n_steps=16)
    with pytest.raises(ValueError, match="sub_rows=4 < radius\\*fuse_steps"):
        p.validate(radius=2, domain_rows=48)
    # the message must point at the escape hatch
    with pytest.raises(ValueError, match="schedule='deep'"):
        p.validate(radius=2, domain_rows=48)


def test_validate_rejects_deep_below_radius():
    p = Plan(tier="resident", schedule="deep", fuse_steps=8, cached_rows=8,
             sub_rows=1, n_steps=16)
    with pytest.raises(ValueError, match="sub_rows=1 < radius"):
        p.validate(radius=2, domain_rows=48)


def test_validate_accepts_deep_where_shallow_fails():
    """The same geometry that kills shallow (sub_rows < r*t) is legal
    deep — depth no longer widens the streaming tile."""
    deep = Plan(tier="resident", schedule="deep", fuse_steps=8,
                cached_rows=8, sub_rows=4, n_steps=16)
    assert deep.validate(radius=2, domain_rows=48) is deep
    shallow = Plan(tier="resident", fuse_steps=8, cached_rows=8, sub_rows=4,
                   n_steps=16)
    with pytest.raises(ValueError):
        shallow.validate(radius=2, domain_rows=48)


def test_validate_runs_in_adapter_dispatch():
    """run_resident raises the validation error, not a kernel assert."""
    spec = get_spec("2d25pt")  # radius 2
    problem = StencilProblem(_domain(spec), spec, 8)
    bad = Plan(tier="resident", fuse_steps=4, cached_rows=8, sub_rows=4,
               n_steps=8)
    with pytest.raises(ValueError, match="radius\\*fuse_steps"):
        problem.run_resident(bad)


def test_plan_schedule_field_roundtrip_and_check():
    p = Plan(tier="resident", schedule="deep", cached_rows=8)
    assert Plan.from_json(p.to_json()) == p
    with pytest.raises(ValueError, match="schedule"):
        Plan(tier="resident", schedule="wavefront")
    # old serialized plans (no schedule key) still load as shallow
    d = p.to_dict()
    d.pop("schedule")
    assert Plan.from_dict(d).schedule == "shallow"


# -- traced structure vs model -------------------------------------------------

def _traced_streamed(spec, steps, t, schedule):
    x = _domain(spec)
    plan = Plan(tier="resident", schedule=schedule, fuse_steps=t,
                cached_rows=16, sub_rows=8, n_steps=steps)
    tr = Tracer(clock=lambda: 0.0)
    with obs.use_tracer(tr):
        execute(StencilProblem(x, spec, steps), plan)
    dma = [dict(e.args) for e in tr.events if e.cat == "dma"]
    chunk = [dict(e.args) for e in tr.events if e.cat == "chunk"]
    assert dma and chunk
    assert sum(c["passes"] for c in chunk) == math.ceil(steps / t)
    streamed = sum(d["passes"] * (d["bytes_read_per_pass"]
                                  + d["bytes_written_per_pass"])
                   for d in dma)
    return streamed + 2 * dma[0]["cached_bytes"]


def _model(spec, steps, t, schedule):
    row_bytes = 64 * 4
    dom = 48 * row_bytes
    if schedule == "deep":
        return gm_bytes_deep(steps, dom, 16 * row_bytes, fuse_steps=t)
    return gm_bytes_fused(steps, dom, 16 * row_bytes, row_bytes=row_bytes,
                          radius=spec.radius, fuse_steps=t)


@pytest.mark.parametrize("schedule", ["shallow", "deep"])
def test_traced_dma_bytes_reproduce_model(schedule):
    """The adapter's per-pass chunk/dma events aggregate to the traffic
    model exactly when t divides n_steps: sum(passes * (read + written))
    + 2*cached == gm."""
    spec = get_spec("2d5pt")
    assert _traced_streamed(spec, 12, 4, schedule) \
        == _model(spec, 12, 4, schedule)


@pytest.mark.parametrize("schedule", ["shallow", "deep"])
def test_traced_dma_bytes_bounded_by_model_on_tails(schedule):
    """On a non-dividing tail the trace is pass-exact (the remainder
    chunk's shallow overlap is narrower than r*t), so the model is an
    upper bound — deep has no overlap term and stays exact."""
    spec = get_spec("2d5pt")
    traced, model = _traced_streamed(spec, 11, 4, schedule), \
        _model(spec, 11, 4, schedule)
    if schedule == "deep":
        assert traced == model
    else:
        assert traced <= model
