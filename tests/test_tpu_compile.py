"""Compile every main-path Pallas kernel for a TPU v5e, at real sizes.

The TPU compiler is installed without a chip: it compiles for a described
``v5e:2x2`` topology and refuses what the chip would refuse (slices not
aligned to the memory tile, more VMEM than a kernel may use, primitives
Mosaic does not lower). Each case asserts that the compiled program holds
the Mosaic kernel (``tpu_custom_call``). The topology is described inside
a fixture, so only the worker that runs this file loads the TPU library.

The kernels that gather a vector by column index (ELL/SELL SpMV, fused
CG/BiCGStab/GMRES) do not compile for a TPU, nor does an SSD scan whose
chunk does not fill the 128-lane tile, nor a stencil kernel over a domain
whose rows are not a whole number of tiles; the tests below pin that the
kernels refuse them there and that the planner, given the v5e, never
offers them.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.exec import BiCGStabProblem, CGProblem, Plan, execute, plan
from repro.exec import plan_candidates
from repro.exec import StencilProblem
from repro.kernels import cg_fused, decode_attn, krylov_fused, spmv_ell
from repro.kernels import spmv_sell, ssm_scan
from repro.kernels import stencil2d as s2d
from repro.kernels.common import get_spec


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _planned(shape, spec, steps, schedule, t):
    """The planner's resident candidate for this schedule and depth."""
    prob = StencilProblem(jax.ShapeDtypeStruct(shape, jnp.float32),
                          get_spec(spec), steps)
    return next(c for c in plan_candidates(prob, chip="tpu_v5e")
                if c.tier == "resident" and c.schedule == schedule
                and c.fuse_steps == t)


def _stencil(kind, spec, side, steps=4, t=1):
    sp = get_spec(spec)
    shape = (side,) * sp.ndim
    if kind == "baseline":
        sub = s2d.default_sub_rows(shape, jnp.float32, sp, schedule="shallow")
        return [shape], lambda x: s2d.stencil_baseline_step(
            x, sp, sub_rows=sub, interpret=False)
    p = _planned(shape, spec, steps, kind, t)
    if kind == "deep":
        assert p.cached_rows < side       # the streamed wavefront kernel
        return [shape], lambda x: s2d.stencil_perks_deep(
            x, sp, steps=steps, cached_rows=p.cached_rows,
            sub_rows=p.sub_rows, fuse_steps=t, interpret=False)
    return [shape], lambda x: s2d.stencil_perks(
        x, sp, steps=steps, cached_rows=p.cached_rows,
        sub_rows=p.sub_rows, fuse_steps=t, interpret=False)


def _planned_smoke():
    """What ``plan()`` picks for chip_smoke.py's 8192^2 2d5pt phase: the
    deep schedule at t=16, whose VMEM request is the largest of any
    planned kernel (resident rows fill the chip)."""
    sp = get_spec("2d5pt")
    shape, steps = (8192, 8192), 20
    p = plan(StencilProblem(jax.ShapeDtypeStruct(shape, jnp.float32), sp,
                            steps), chip="tpu_v5e")
    assert (p.tier, p.schedule, p.fuse_steps) == ("resident", "deep", 16)
    return [shape], lambda x: s2d.stencil_perks_deep(
        x, sp, steps=steps, cached_rows=p.cached_rows, sub_rows=p.sub_rows,
        fuse_steps=p.fuse_steps, interpret=False)


def _ssm():
    T, H, P, N = 2048, 48, 64, 128
    shapes = [(T, H, P), (T, H), (H,), (T, N), (T, N), (H,)]
    return shapes, lambda *a: ssm_scan.ssm_scan(*a, chunk=128,
                                                interpret=False)


def _decode():
    B, S, HQ, HKV, D = 8, 4096, 14, 2, 64
    shapes = [(B, HQ, D), (B, S, HKV, D), (B, S, HKV, D)]
    return shapes, lambda q, k, v: decode_attn.decode_attention(
        q, k, v, interpret=False), jnp.bfloat16


CASES = {
    # fully VMEM-resident domains (the planner caches every row)
    "resident_2d5pt_1024": lambda: _stencil("shallow", "2d5pt", 1024),
    "resident_3d7pt_256": lambda: _stencil("shallow", "3d7pt", 256),
    # 8192^2 f32 (256 MiB, twice the VMEM): partial residency, streamed
    "shallow_t1_2d5pt_8192": lambda: _stencil("shallow", "2d5pt", 8192),
    "shallow_t4_2d5pt_8192": lambda: _stencil("shallow", "2d5pt", 8192, t=4),
    "deep_t2_2ds9pt_8192": lambda: _stencil("deep", "2ds9pt", 8192, t=2),
    "deep_t16_2d5pt_8192_planned": _planned_smoke,
    "baseline_2d5pt_8192": lambda: _stencil("baseline", "2d5pt", 8192),
    "ssm_scan": _ssm,
    "decode_attention": _decode,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    shapes, fn, *dtype = CASES[case]()
    dtype = dtype[0] if dtype else jnp.float32
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- gather kernels: refused on a TPU ------------------------------------------

def _ell(side=8):
    data, cols = spmv_ell.poisson2d_ell(side)
    b = np.ones(side * side, np.float32)
    return jnp.asarray(data), jnp.asarray(cols), jnp.asarray(b)


GATHER_KERNELS = {
    "spmv_ell": lambda d, c, b: spmv_ell.spmv_ell(d, c, b, interpret=False),
    "spmv_sell": lambda d, c, b: spmv_sell.spmv_sell(
        d.reshape(-1), c.reshape(-1), jnp.zeros(1, jnp.int32),
        jnp.ones(1, jnp.int32), b, c=8, k_max=5, interpret=False),
    "cg_fused": lambda d, c, b: cg_fused.cg_fused(
        d, c, b, iters=2, interpret=False),
    "bicgstab_fused": lambda d, c, b: krylov_fused.bicgstab_fused(
        d, c, b, iters=2, interpret=False),
    "gmres_cycle_fused": lambda d, c, b: krylov_fused.gmres_cycle_fused(
        d, c, b, b, m=2, interpret=False),
}


@pytest.mark.parametrize("kernel", sorted(GATHER_KERNELS))
def test_gather_kernel_refuses_mosaic(kernel):
    with pytest.raises(NotImplementedError, match="Mosaic"):
        GATHER_KERNELS[kernel](*_ell())


@pytest.mark.parametrize("cls", [CGProblem, BiCGStabProblem])
def test_tpu_planner_offers_no_resident_krylov(cls, monkeypatch):
    prob = cls.from_ell(*_ell(), 10)
    assert any(c.tier == "resident" for c in plan_candidates(prob))
    cands = plan_candidates(prob, chip="tpu_v5e")
    assert cands and all(c.tier != "resident" for c in cands)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="Mosaic"):
        execute(prob, Plan(tier="resident", policy="MIX", n_steps=10))


@pytest.mark.parametrize("t,chunk,resident", [(256, 128, True),
                                              (256, 32, False),
                                              (96, 128, True)])
def test_tpu_planner_offers_resident_ssd_only_for_lane_chunks(
        t, chunk, resident):
    """On a TPU the SSD chunk sits on the lane axis: the resident tier is
    offered only for whole 128-lane chunks or a single whole-sequence
    chunk, and the kernel refuses the rest before Mosaic does."""
    from repro.exec.ml import SSMScanProblem
    shapes = {"x": (t, 2, 64), "dt": (t, 2), "a": (2,), "b": (t, 16),
              "c": (t, 16), "d": (2,)}
    prob = SSMScanProblem(chunk=chunk, **{
        k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()})
    assert any(c.tier == "resident" for c in plan_candidates(prob))
    cands = plan_candidates(prob, chip="tpu_v5e")
    assert any(c.tier == "resident" for c in cands) == resident
    if not resident:
        with pytest.raises(ValueError, match="multiple of 128"):
            ssm_scan.ssm_scan(*(jnp.zeros(s) for s in shapes.values()),
                              chunk=chunk, interpret=False)


@pytest.mark.parametrize("rows,cols,resident", [(1024, 512, True),
                                                (1001, 512, False),
                                                (8196, 8192, False)])
def test_tpu_planner_offers_resident_stencil_only_for_tiled_rows(
        rows, cols, resident):
    """On a TPU every leading-axis window of a resident stencil kernel is
    a whole number of 8-row f32 tiles, the domain's own extent included,
    whether it is held whole (1001 rows) or in part (8196 rows); the CPU
    runs any extent interpreted."""
    sp = get_spec("2d5pt")
    prob = StencilProblem(jax.ShapeDtypeStruct((rows, cols), jnp.float32),
                          sp, 8)
    assert any(c.tier == "resident" for c in plan_candidates(prob))
    cands = plan_candidates(prob, chip="tpu_v5e")
    assert any(c.tier == "resident" for c in cands) == resident
    if not resident:
        with pytest.raises(ValueError, match="multiple of 8 rows"):
            s2d.stencil_perks(jnp.zeros((rows, 8), jnp.float32), sp,
                              steps=2, cached_rows=0, sub_rows=64,
                              interpret=False)
