"""Batched multi-tenant execution (repro.exec.batch, DESIGN.md §8).

The load-bearing contract: a B-wide batched dispatch computes exactly
what B sequential single-instance dispatches compute — bit-identically —
on every tier, over all 13 stencil specs and real sparse-registry CG
operators. Plus the planner's B-awareness: per-instance cache shrinks as
B grows (VMEM/B), the shared CG matrix does not scale with B, and Plans
carry ``batch`` through the JSON round-trip.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec import (
    BatchedProblem,
    CGProblem,
    Plan,
    StencilProblem,
    execute,
    execute_sequential,
    plan,
    plan_candidates,
)
from repro.kernels.common import BENCHMARKS, get_spec
from repro.solvers import cg as cgs

B = 3
STEPS = 3


def _domains(spec, b=B):
    shape = (48, 64) if spec.ndim == 2 else (24, 16, 32)
    return [jax.random.normal(jax.random.key(i), shape, jnp.float32)
            for i in range(b)]


def _stencil_batch(name, b=B):
    spec = get_spec(name)
    insts = [StencilProblem(x, spec, STEPS) for x in _domains(spec, b)]
    return insts, BatchedProblem.from_instances(insts)


def _assert_split_equal(batched_result, seq_results, bp):
    for got, want in zip(bp.split(batched_result), seq_results):
        got_l = jax.tree.leaves(got)
        want_l = jax.tree.leaves(want)
        assert len(got_l) == len(want_l)
        for g, w in zip(got_l, want_l):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- bit-exact equivalence: all 13 stencil specs --------------------------------

@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_batched_stencil_matches_sequential(name):
    insts, bp = _stencil_batch(name)
    rows = insts[0].x.shape[0] // 2
    plans = [
        Plan(tier="host_loop"),
        Plan(tier="device_loop"),
        Plan(tier="resident", cached_rows=rows, sub_rows=8),
    ]
    for single in plans:
        batched = dataclasses.replace(single, batch=B)
        out = execute(bp, batched)
        seq = execute_sequential(insts, single)
        _assert_split_equal(out, seq, bp)


def test_batched_stencil_fused_resident_matches_sequential():
    insts, bp = _stencil_batch("2d5pt")
    single = Plan(tier="resident", cached_rows=24, sub_rows=32, fuse_steps=2)
    out = execute(bp, dataclasses.replace(single, batch=B))
    _assert_split_equal(out, execute_sequential(insts, single), bp)


# -- bit-exact equivalence: sparse-registry CG ----------------------------------

@pytest.mark.parametrize("dataset", ["poisson2d_small", "fem_band_8k"])
def test_batched_cg_matches_sequential(dataset):
    data, cols = cgs.load_dataset(dataset)
    bs = [jax.random.normal(jax.random.key(10 + i), (data.shape[0],),
                            jnp.float32) for i in range(B)]
    insts = [CGProblem.from_ell(data, cols, b, 4) for b in bs]
    bp = BatchedProblem.from_instances(insts)
    for single in (Plan(tier="host_loop"), Plan(tier="device_loop")):
        out = execute(bp, dataclasses.replace(single, batch=B))
        seq = execute_sequential(insts, single)
        _assert_split_equal(out, seq, bp)


def test_batched_cg_resident_matches_sequential():
    data, cols = cgs.load_dataset("poisson_64")
    bs = [jax.random.normal(jax.random.key(20 + i), (data.shape[0],),
                            jnp.float32) for i in range(B)]
    insts = [CGProblem.from_ell(data, cols, b, 5) for b in bs]
    bp = BatchedProblem.from_instances(insts)
    single = Plan(tier="resident", policy="MIX", block_rows=256)
    out = execute(bp, dataclasses.replace(single, batch=B))
    _assert_split_equal(out, execute_sequential(insts, single), bp)


def test_batched_cg_early_stop_converges_all_instances():
    data, cols = cgs.load_dataset("poisson_64")
    bs = [jax.random.normal(jax.random.key(30 + i), (data.shape[0],),
                            jnp.float32) for i in range(B)]
    insts = [CGProblem.from_ell(data, cols, b, 500, tol=1e-10) for b in bs]
    bp = BatchedProblem.from_instances(insts)
    dev = next(c for c in plan_candidates(bp) if c.tier == "device_loop")
    assert dev.sync_every is not None and dev.batch == B
    x, rr = execute(bp, dev)
    assert x.shape[0] == B
    for i, b in enumerate(bs):
        assert float(rr[i]) < 1e-10 * float(jnp.vdot(b, b)) * 10


def test_batched_on_sync_is_one_stacked_reduction(monkeypatch):
    """The batched convergence check must evaluate ALL lanes with one
    device-side vmapped reduction — the per-instance host callbacks are
    never invoked (previously: B host transfers per sync point)."""
    data, cols = cgs.load_dataset("poisson_64")
    bs = [jax.random.normal(jax.random.key(60 + i), (data.shape[0],),
                            jnp.float32) for i in range(B)]
    insts = [CGProblem.from_ell(data, cols, b, 500, tol=1e-10) for b in bs]
    bp = BatchedProblem.from_instances(insts)

    def _boom(self):
        raise AssertionError("per-instance on_sync must not be consulted")

    monkeypatch.setattr(CGProblem, "on_sync", _boom)
    vec, params = bp.convergence()
    lane_vec = vec(bp.initial_state(), params)
    assert lane_vec.shape == (B,) and lane_vec.dtype == jnp.bool_
    check = bp.on_sync()
    assert check(bp.initial_state(), 0) is False
    x, rr = execute(bp, Plan(tier="device_loop", sync_every=25, batch=B))
    for i, b in enumerate(bs):
        assert float(rr[i]) < 1e-10 * float(jnp.vdot(b, b)) * 10


def test_lane_runner_retirement_bit_exact_vs_sequential():
    """LaneRunner's masked group step with staggered admission and
    per-lane early retirement computes exactly what each instance
    computes alone under the same chunked device loop."""
    from repro.exec.batch import LaneRunner

    data, cols = cgs.load_dataset("poisson_64")
    chunk, n = 5, 400
    insts = [CGProblem.from_ell(
        data, cols,
        jax.random.normal(jax.random.key(70 + i), (data.shape[0],),
                          jnp.float32), n, tol=1e-8) for i in range(3)]
    runner = LaneRunner(insts[0], width=4)
    lanes = runner.fresh()
    group = jax.jit(runner.step_fn())
    lanes = runner.admit(lanes, 0, insts[0])
    lanes = runner.admit(lanes, 2, insts[1])
    admitted_at = {0: 0, 2: 0}
    done = {}
    barrier = 0
    while len(done) < 3:
        carry = (lanes.state, lanes.steps_done)
        for _ in range(chunk):
            carry = group(carry)
        lanes = dataclasses.replace(lanes, state=carry[0],
                                    steps_done=carry[1])
        barrier += 1
        conv = runner.convergence_vector(lanes)
        for lane, inst_i in ((0, 0), (2, 1), (1, 2)):
            if inst_i in done or lane not in admitted_at:
                continue
            steps = min((barrier - admitted_at[lane]) * chunk, n)
            if bool(conv[lane]) or steps >= n:
                done[inst_i] = (runner.harvest(lanes, lane), steps)
                lanes = runner.retire(lanes, lane)
                if 2 not in done and 1 not in admitted_at:
                    # mid-flight swap-in: instance 2 takes the freed lane 1
                    lanes = runner.admit(lanes, 1, insts[2])
                    admitted_at[1] = barrier
    for i, inst in enumerate(insts):
        want = execute(inst, Plan(tier="device_loop", sync_every=chunk))
        got, steps = done[i]
        assert steps < n                     # all retired early
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_lane_runner_rejects_incompatible_admission():
    from repro.exec.batch import LaneRunner

    insts, bp = _stencil_batch("2d5pt")
    runner = LaneRunner(insts[0], width=2)
    other = StencilProblem(
        jax.random.normal(jax.random.key(9), (24, 32), jnp.float32),
        get_spec("2d5pt"), STEPS)            # same family, wrong shape
    with pytest.raises(ValueError, match="batch key"):
        runner.admit(runner.fresh(), 0, other)
    with pytest.raises(TypeError, match="single-instance"):
        LaneRunner(bp, width=2)


# -- batched oracle / split / padding -------------------------------------------

def test_batched_oracle_and_split_shapes():
    insts, bp = _stencil_batch("2d5pt")
    orc = bp.oracle()
    assert orc.shape == (B,) + insts[0].x.shape
    for i, inst in enumerate(insts):
        np.testing.assert_array_equal(np.asarray(orc[i]),
                                      np.asarray(inst.oracle()))
    out = execute(bp, Plan(tier="host_loop", batch=B))
    assert len(bp.split(out)) == B


def test_padding_replicates_and_is_dropped():
    insts, _ = _stencil_batch("2d5pt", b=2)
    bp = BatchedProblem.from_instances(insts, pad_to=4)
    assert bp.batch == 4 and bp.pad == 2
    out = execute(bp, Plan(tier="device_loop", batch=4))
    seq = execute_sequential(insts, Plan(tier="device_loop"))
    split = bp.split(out)
    assert len(split) == 2          # padded lanes dropped
    _assert_split_equal(out, seq, bp)


def test_with_payload_preserves_padding():
    insts, _ = _stencil_batch("2d5pt", b=2)
    bp = BatchedProblem.from_instances(insts, pad_to=4)
    clone = bp.with_payload(bp.payload())
    assert clone.batch == 4 and clone.pad == 2
    assert len(clone.split(clone.oracle())) == 2
    np.testing.assert_array_equal(np.asarray(clone.payload_stack),
                                  np.asarray(bp.payload_stack))


# -- construction + executor validation -----------------------------------------

def test_batched_problem_rejects_mixed_instances():
    a = StencilProblem(_domains(get_spec("2d5pt"))[0], get_spec("2d5pt"),
                       STEPS)
    b = StencilProblem(_domains(get_spec("2d9pt"))[0], get_spec("2d9pt"),
                       STEPS)
    with pytest.raises(ValueError, match="batch-compatible"):
        BatchedProblem.from_instances([a, b])
    with pytest.raises(ValueError, match="nest"):
        BatchedProblem.from_instances([BatchedProblem.from_instances([a])])
    with pytest.raises(ValueError, match="pad_to"):
        BatchedProblem.from_instances([a, a], pad_to=1)
    with pytest.raises(ValueError):
        BatchedProblem.from_instances([])


def test_executor_rejects_batch_mismatch():
    insts, bp = _stencil_batch("2d5pt")
    with pytest.raises(ValueError, match="batch"):
        execute(bp, Plan(tier="device_loop"))          # plan.batch=1
    with pytest.raises(ValueError, match="batch"):
        execute(insts[0], Plan(tier="device_loop", batch=B))


def test_plan_batch_field_round_trip_and_validation():
    p = Plan(tier="device_loop", batch=8, n_steps=5)
    assert Plan.from_json(p.to_json()) == p
    assert Plan.from_dict(p.to_dict()).batch == 8
    with pytest.raises(ValueError):
        Plan(tier="device_loop", batch=0)


# -- planner batch-awareness ----------------------------------------------------

def test_planner_per_instance_cache_shrinks_with_batch():
    """VMEM/B per instance: larger batches never cache MORE rows per
    instance, and eventually demote the resident tier's residency. Once
    not even the kernel's streaming buffers fit an instance's share, the
    resident candidate is gone (counted as zero rows)."""
    spec = get_spec("2d9pt")
    problem = StencilProblem(
        jax.ShapeDtypeStruct((4096, 2048), jnp.float32), spec, 100)
    prev = None
    for b in (1, 4, 16, 64, 256):
        cands = plan_candidates(problem, batch=b)
        assert all(c.batch == b for c in cands)
        res = next((c for c in cands
                    if c.tier == "resident" and c.fuse_steps == 1), None)
        rows = 0 if res is None else res.cached_rows
        if prev is not None:
            assert rows <= prev, (b, res)
        prev = rows
    assert prev == 0    # the sweep must reach full demotion


def test_autotune_batch_sweep_returns_per_width_winners():
    from repro.exec import autotune_batch_sweep
    insts, _ = _stencil_batch("2d5pt", b=4)
    res = autotune_batch_sweep(insts, batches=(1, 4), top_k=2, warmup=0,
                               iters=1)
    assert set(res) == {1, 4}
    for b, r in res.items():
        assert r.best.batch == b
        assert all(row.measured_s > 0 for row in r.table)
    with pytest.raises(ValueError, match="instances"):
        autotune_batch_sweep(insts, batches=(8,))


def test_planner_infers_batch_from_batched_problem():
    insts, bp = _stencil_batch("2d5pt")
    chosen = plan(bp)
    assert chosen.batch == B
    assert chosen.problem == bp.name
    with pytest.raises(ValueError, match="conflicts"):
        plan_candidates(bp, batch=B + 1)
    # the chosen plan actually executes the batched problem
    out = execute(bp, chosen)
    assert len(bp.split(out)) == B


def test_batched_cg_working_set_shares_matrix():
    """B-scaled working set: Krylov vectors scale by B, A does not."""
    data, cols = cgs.load_dataset("poisson_64")
    b0 = jax.random.normal(jax.random.key(0), (data.shape[0],), jnp.float32)
    insts = [CGProblem.from_ell(data, cols, b0, 4) for _ in range(4)]
    bp = BatchedProblem.from_instances(insts)
    single = {a.name: a.bytes for a in insts[0].cacheable_arrays()}
    batched = {a.name: a.bytes for a in bp.cacheable_arrays()}
    assert batched["A"] == single["A"]
    for name in ("r", "p", "x", "Ap"):
        assert batched[name] == 4 * single[name]


def test_batch_keys_separate_operators_and_families():
    data, cols = cgs.load_dataset("poisson_64")
    data2 = data + 0.0       # same values, DIFFERENT operator object
    b0 = jnp.ones((data.shape[0],), jnp.float32)
    p1 = CGProblem.from_ell(data, cols, b0, 4)
    p2 = CGProblem.from_ell(data2, cols, b0, 4)
    assert p1.batch_key() != p2.batch_key()
    s1, s2 = (StencilProblem(_domains(get_spec(n))[0], get_spec(n), STEPS)
              for n in ("2d5pt", "3d7pt"))
    assert s1.batch_key() != s2.batch_key()
    assert p1.batch_key() != s1.batch_key()


# -- distributed tier -----------------------------------------------------------

def test_batched_distributed_matches_sequential(dist_run):
    """One vmapped shard_map program: every instance's halo/psum rides
    the same collective round, results stay bit-exact per instance."""
    out = dist_run("""
    import warnings, jax, jax.numpy as jnp, numpy as np, json
    from repro.dist.mesh import make_mesh
    from repro.exec import (BatchedProblem, CGProblem, Plan, StencilProblem,
                            execute, execute_sequential)
    from repro.kernels.common import get_spec
    spec = get_spec("2d5pt")
    mesh = make_mesh((4,), ("data",))
    B = 3
    xs = [jax.random.normal(jax.random.key(i), (32, 16), jnp.float32)
          for i in range(B)]
    insts = [StencilProblem(x, spec, 5) for x in xs]
    bp = BatchedProblem.from_instances(insts)
    exact = {}
    for t in (1, 2):
        single = Plan(tier="distributed", shard_axis="data", fuse_steps=t)
        out = execute(bp, Plan(tier="distributed", batch=B,
                               shard_axis="data", fuse_steps=t), mesh=mesh)
        seq = execute_sequential(insts, single, mesh=mesh)
        exact[f"stencil_t{t}"] = all(
            np.array_equal(np.asarray(out[i]), np.asarray(seq[i]))
            for i in range(B))
    from repro.solvers import cg as cgs
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        data, cols = cgs.load_dataset("poisson_64")
    bs = [jax.random.normal(jax.random.key(10 + i), (data.shape[0],),
                            jnp.float32) for i in range(B)]
    cinsts = [CGProblem.from_ell(data, cols, b, 4) for b in bs]
    cbp = BatchedProblem.from_instances(cinsts)
    for fused in (False, True):
        single = Plan(tier="distributed", shard_axis="data",
                      fuse_reductions=fused)
        xb, rrb = execute(cbp, Plan(tier="distributed", batch=B,
                                    shard_axis="data",
                                    fuse_reductions=fused), mesh=mesh)
        seq = execute_sequential(cinsts, single, mesh=mesh)
        exact[f"cg_fused{int(fused)}"] = all(
            np.array_equal(np.asarray(xb[i]), np.asarray(seq[i][0]))
            and float(rrb[i]) == float(seq[i][1]) for i in range(B))
    print(json.dumps(exact))
    """)
    assert all(out.values()), out


# -- deprecation hygiene of the new surface -------------------------------------

def test_batched_path_emits_no_deprecation_warnings():
    """The batched tier is pure repro.exec — it must never route through
    a legacy shim."""
    insts, bp = _stencil_batch("2d5pt")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        execute(bp, plan(bp))
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]
