"""PERKS execution-model invariants + hypothesis property tests."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-dep shim (tests/_hyp.py)

from repro.core import perks
from repro.core.cache_policy import (CacheableArray, gm_bytes_fused,
                                     plan_caching,
                                     cg_arrays, stencil_arrays,
                                     stencil_shard_arrays)
from repro.core.hardware import A100, TPU_V5E
from repro.core.perf_model import (project_perks, projected_speedup,
                                   gm_bytes_accessed, efficiency)
from repro.kernels import ref
from repro.kernels.common import get_spec


# -- execution tiers compute identical results ---------------------------------

def test_host_device_chunked_identical():
    spec = get_spec("2d5pt")
    x = jax.random.normal(jax.random.key(0), (32, 64), jnp.float32)
    step = functools.partial(ref.stencil_step, spec=spec)
    a = perks.host_loop(step, 6, donate=False)(x)
    b = perks.device_loop(step, 6, donate=False)(x)
    c = perks.chunked_loop(step, 6, sync_every=2, donate=False)(x)
    np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(a, c, atol=1e-6)


def test_chunked_early_stop():
    calls = []
    step = lambda s: s + 1
    run = perks.chunked_loop(step, 100, sync_every=10, donate=False,
                             on_sync=lambda s, k: calls.append(k) or s >= 30)
    out = run(jnp.int32(0))
    assert int(out) == 30
    assert calls == [10, 20, 30]


def test_chunked_early_stop_state_is_partially_advanced():
    """Early exit must return the state as of the sync point it stopped at —
    the partially-advanced array, not the fully-run one."""
    spec = get_spec("2d5pt")
    x = jax.random.normal(jax.random.key(1), (16, 64), jnp.float32)
    step = functools.partial(ref.stencil_step, spec=spec)
    out = perks.chunked_loop(step, 100, sync_every=3,
                             on_sync=lambda s, k: k >= 6)(x)
    want = perks.device_loop(step, 6, donate=False)(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_chunked_loop_non_dividing_runs_exact_step_count():
    """sync_every that does not divide n_steps: the tail chunk fuses only
    the remainder — exactly n_steps applications, ceil(n/k) dispatches."""
    calls = []
    run = perks.chunked_loop(lambda s: s + 1, 7, sync_every=3, donate=False,
                             on_sync=lambda s, k: calls.append(k) or False)
    assert int(run(jnp.int32(0))) == 7
    assert calls == [3, 6, 7]


def test_host_loop_on_sync_checks_every_step():
    """The baseline tier is back on the host after EVERY dispatch, so a
    convergence callback fires each step and stops the loop early."""
    calls = []
    run = perks.host_loop(lambda s: s + 1, 100, donate=False,
                          on_sync=lambda s, k: calls.append(k) or s >= 3)
    out = run(jnp.int32(0))
    assert int(out) == 3
    assert calls == [1, 2, 3]


def test_persistent_host_loop_threads_on_sync():
    """persistent() must not drop on_sync on the fuse_steps=1 HOST_LOOP
    path (the hole that made convergence-declared problems run all
    n_steps on the baseline tier)."""
    syncs = []
    cfg = perks.PerksConfig(execution=perks.Execution.HOST_LOOP)
    run = perks.persistent(lambda s: s + 1, 10, cfg,
                           on_sync=lambda s, k: syncs.append(k) or s >= 4)
    assert int(run(jnp.int32(0))) == 4
    assert syncs == [1, 2, 3, 4]


def test_chunked_on_barrier_can_replace_state_and_stop():
    """The scheduler hook may rewrite the state at a barrier (lane
    admission/retirement) and owns termination in open-ended mode."""
    seen = []

    def barrier(state, k):
        seen.append((int(state), k))
        if k >= 6:
            return state, True
        return state * 10, False           # scheduler swaps the state

    run = perks.chunked_loop(lambda s: s + 1, None, sync_every=2,
                             donate=False, on_barrier=barrier)
    out = run(jnp.int32(0))
    # chunks: 0+2=2 -> swap 20 -> 20+2=22 -> swap 220 -> 220+2=222 stop
    assert seen == [(2, 2), (22, 4), (222, 6)]
    assert int(out) == 222
    with pytest.raises(ValueError, match="on_barrier"):
        perks.chunked_loop(lambda s: s + 1, None, sync_every=2)


def test_chunked_on_barrier_runs_before_on_sync_in_bounded_mode():
    order = []
    run = perks.chunked_loop(
        lambda s: s + 1, 9, sync_every=3, donate=False,
        on_barrier=lambda s, k: order.append(("barrier", k)) or (s, False),
        on_sync=lambda s, k: order.append(("sync", k)) or s >= 6)
    assert int(run(jnp.int32(0))) == 6
    assert order == [("barrier", 3), ("sync", 3),
                     ("barrier", 6), ("sync", 6)]


def test_scan_loop_collects_outputs():
    step = lambda s, _: (s * 2, s)
    final, outs = perks.scan_loop(step, 4, donate=False)(jnp.float32(1.0))
    assert float(final) == 16.0
    np.testing.assert_allclose(outs, [1, 2, 4, 8])


# -- temporal blocking (fuse_steps) ----------------------------------------------

def test_perks_config_validates_fuse_steps():
    with pytest.raises(ValueError):
        perks.PerksConfig(fuse_steps=0)
    with pytest.raises(ValueError):
        perks.PerksConfig(sync_every=0)
    assert perks.PerksConfig(fuse_steps=4).fuse_steps == 4


def test_host_loop_fuse_steps_cuts_dispatch_count():
    """HOST_LOOP with fuse_steps=t: the dispatch is the barrier, so the
    runner must come back to the host only ceil(n/t) times."""
    syncs = []
    cfg = perks.PerksConfig(execution=perks.Execution.HOST_LOOP, fuse_steps=4)
    run = perks.persistent(lambda s: s + 1, 10, cfg,
                           on_sync=lambda s, k: syncs.append(k) or False)
    assert int(run(jnp.int32(0))) == 10
    assert syncs == [4, 8, 10]  # ceil(10/4) = 3 barriers


# -- cache policy properties -----------------------------------------------------

def test_paper_priorities():
    """§III-B: interior > boundary > halo; for CG, r > A."""
    arrays = stencil_arrays(1000, 100, 50)
    plan = plan_caching(arrays, 600)
    assert plan.assignments[0].array.name == "interior"
    assert plan.fraction_of("halo") == 0.0
    cg = plan_caching(cg_arrays(1000, 50_000, 4), 10_000)
    assert cg.assignments[0].array.name == "r"
    names = [a.array.name for a in cg.assignments]
    assert names.index("r") < names.index("A") if "A" in names else True


@given(
    arrays=st.lists(
        st.tuples(st.integers(1, 10**7), st.floats(0, 4), st.floats(0, 4),
                  st.booleans()),
        min_size=1, max_size=8),
    budget=st.integers(0, 10**7),
)
@settings(max_examples=60, deadline=None)
def test_cache_plan_invariants(arrays, budget):
    cas = [CacheableArray(f"a{i}", b, l, s, inter_block_dep=dep)
           for i, (b, l, s, dep) in enumerate(arrays)]
    plan = plan_caching(cas, budget)
    # never exceeds budget
    assert plan.cached_bytes <= budget
    # never caches a zero-value array
    for a in plan.assignments:
        assert a.array.traffic_saved_per_byte() > 0
        assert 0 < a.cached_bytes <= a.array.bytes
    # greedy is optimal for the fractional knapsack: density non-increasing
    ds = [a.array.traffic_saved_per_byte() for a in plan.assignments]
    assert all(x >= y - 1e-9 for x, y in zip(ds, ds[1:]))
    # budget exhausted OR everything cacheable is cached
    total_cacheable = sum(a.bytes for a in cas
                          if a.traffic_saved_per_byte() > 0)
    assert (plan.cached_bytes == min(budget, total_cacheable))


@given(st.integers(1, 1000), st.integers(0, 10**6), st.integers(0, 10**6))
@settings(max_examples=60, deadline=None)
def test_gm_traffic_monotone_in_cache(n_steps, domain, cached):
    cached = min(cached, domain)
    base = gm_bytes_accessed(n_steps, domain, 0)
    with_cache = gm_bytes_accessed(n_steps, domain, cached)
    assert with_cache <= base + 1e-9
    full = gm_bytes_accessed(n_steps, domain, domain)
    assert full <= with_cache + 1e-9
    assert full == 2 * domain  # initial load + final store only


def test_temporal_block_widens_uncached_ring():
    """fuse_steps=t widens the boundary/halo ring r -> r*t, shrinking the
    fully-elidable interior (generalized Eq. 5's uncached ring)."""
    a1 = {a.name: a.bytes for a in stencil_shard_arrays(128, 10, 2)}
    a4 = {a.name: a.bytes for a in stencil_shard_arrays(128, 10, 2,
                                                        fuse_steps=4)}
    assert a1["interior"] == (128 - 4) * 10 and a4["interior"] == (128 - 16) * 10
    assert a4["boundary"] == 4 * a1["boundary"]
    assert a4["halo"] == 4 * a1["halo"]


def test_gm_bytes_fused_recovers_and_beats_eq5():
    dom, cached, rb, r, N = 10_000, 0, 10, 2, 100
    base = gm_bytes_fused(N, dom, cached, row_bytes=rb, radius=r, fuse_steps=1)
    assert base == N * (2 * dom + 2 * r * rb)  # Eq. 5 + per-step halo re-read
    fused = gm_bytes_fused(N, dom, cached, row_bytes=rb, radius=r,
                           fuse_steps=4)
    assert fused < base            # t x fewer passes dominates the overlap
    full = gm_bytes_fused(N, dom, dom, row_bytes=rb, radius=r, fuse_steps=4)
    assert full == 2 * dom         # fully cached: initial load + final store


# -- performance model (paper §IV-B worked examples) -----------------------------

def test_paper_worked_example_a100():
    """Reproduce T_gm = 9900.70us, T_halo = 871.22us and P = 876.09 GCells/s
    from §IV-B. (The halo bytes follow the paper's computed 871.22us —
    1000 * 2 * 216 * (136*2 + 256*2) * 4B — the printed formula carries an
    extra factor 2 that their own arithmetic does not apply.)"""
    p = project_perks(A100, n_steps=1000, domain_cells=3072 * 3072,
                      dtype_bytes=4, cached_cells=3072 * 2448,
                      halo_bytes_per_step=2 * 216 * (136 * 2 + 256 * 2) * 4)
    assert abs(p.t_gm * 1e6 - 9900.70) < 1.0
    assert abs(p.t_gm_halo * 1e6 - 871.22) < 5.0
    assert abs(p.cells_per_s / 1e9 - 876.09) < 5.0


def test_projected_speedup_increases_with_cache():
    s_half = projected_speedup(TPU_V5E, n_steps=100, domain_cells=10**6,
                               dtype_bytes=4, cached_cells=5 * 10**5)
    s_full = projected_speedup(TPU_V5E, n_steps=100, domain_cells=10**6,
                               dtype_bytes=4, cached_cells=10**6)
    assert 1.0 < s_half < s_full
    # fully cached: HBM pays only 2D, but Eq. 10's max() moves the bound to
    # the on-chip bandwidth term — speedup saturates at bw_ratio/2, not N
    assert s_full > 20
    full = project_perks(TPU_V5E, n_steps=100, domain_cells=10**6,
                         dtype_bytes=4, cached_cells=10**6)
    assert full.bound == "onchip_memory"


@given(st.floats(0, 10), st.floats(0.01, 10))
@settings(max_examples=40, deadline=None)
def test_efficiency_clamps(c_sw, c_hw):
    e = efficiency(c_sw, c_hw)
    assert 0.0 <= e <= 1.0
    if c_sw >= c_hw:
        assert e == 1.0
