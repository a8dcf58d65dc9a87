"""Execution-tier equivalence across the whole stencil zoo.

``core/perks.py`` promises that HOST_LOOP, DEVICE_LOOP and RESIDENT
compute bit-identical results (DESIGN.md §2). This asserts it for every
``StencilSpec`` in ``kernels/common.py``:

  * host loop == device loop == chunked loop: exactly equal (same step
    function, only the dispatch structure differs);
  * RESIDENT (fully VMEM-resident kernel): exactly equal — the kernel body
    applies the same shifted-slice sum as the oracle;
  * RESIDENT with partial caching (the streamed PERKS kernel): equal to
    <= 1 ulp. XLA is free to contract mul+add into FMA differently for the
    subtiled slices, so bit-equality is not guaranteed there by any
    backend; the tolerance below is two ulps of the O(1) cell values.

Temporal blocking (DESIGN.md §4): ``fuse_steps=t`` performs the exact
per-step arithmetic through wider windows, so the same ulp caveat
applies — fused results must agree with per-step execution to <= 2 ulp
per 5 steps, distributed (wide-halo exchange) and resident (multi-step
HBM pass) alike. The distributed variant must additionally issue exactly
ceil(steps/t) halo exchanges, asserted by counting ``ppermute``s in the
traced jaxpr (scan trip counts multiplied through).
"""
import functools
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perks
from repro.kernels import ref
from repro.kernels.common import BENCHMARKS, get_spec
from repro.solvers import stencil

STEPS = 4


def _domain(spec):
    shape = (48, 64) if spec.ndim == 2 else (24, 16, 32)
    return jax.random.normal(jax.random.key(0), shape, jnp.float32)


@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_tiers_bit_identical(name):
    spec = get_spec(name)
    x = _domain(spec)
    host = stencil.run_host_loop(x, spec, STEPS)
    device = stencil.run_device_loop(x, spec, STEPS)
    resident = stencil.run_resident(x, spec, STEPS,
                                    cached_rows=x.shape[0])
    step = functools.partial(ref.stencil_step, spec=spec)
    chunked = perks.chunked_loop(step, STEPS, sync_every=2)(x)
    np.testing.assert_array_equal(np.asarray(host), np.asarray(device))
    np.testing.assert_array_equal(np.asarray(host), np.asarray(chunked))
    np.testing.assert_array_equal(np.asarray(host), np.asarray(resident))


@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_partial_caching_within_ulp(name):
    spec = get_spec(name)
    x = _domain(spec)
    device = stencil.run_device_loop(x, spec, STEPS)
    perks_partial = stencil.run_resident(x, spec, STEPS,
                                         cached_rows=x.shape[0] // 2,
                                         sub_rows=8)
    np.testing.assert_allclose(np.asarray(perks_partial), np.asarray(device),
                               rtol=0, atol=2.5e-7)


# -- temporal blocking: resident tier (multi-step HBM passes) -------------------

@pytest.mark.parametrize("name", sorted(BENCHMARKS))
@pytest.mark.parametrize("fuse", [2, 4])
def test_resident_fused_matches_per_step(name, fuse):
    """5 steps with t steps per HBM pass == 5 per-step passes (exercises the
    remainder pass: 5 = 2+2+1 for t=2, 4+1 for t=4)."""
    spec = get_spec(name)
    x = _domain(spec)
    steps = 5
    base = stencil.run_resident(x, spec, steps, cached_rows=x.shape[0] // 2,
                                sub_rows=32)
    fused = stencil.run_resident(x, spec, steps, cached_rows=x.shape[0] // 2,
                                 sub_rows=32, fuse_steps=fuse)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=0, atol=5e-7)
    # and against the jnp oracle at the usual kernel tolerance
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(ref.stencil_run(x, spec, steps)),
                               rtol=0, atol=1e-5)


def test_fusion_schedule_covers_steps_with_ceil_barriers():
    for steps in (1, 2, 5, 7, 12):
        for t in (1, 2, 3, 4, 16):
            sched = stencil.fusion_schedule(steps, t)
            assert sum(n * ct for n, ct in sched) == steps
            assert sum(n for n, _ in sched) == -(-steps // t)  # ceil
            assert all(ct <= t for _, ct in sched)


# -- temporal blocking: distributed tier (wide-halo exchange) -------------------

_DIST_FUSED = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.kernels.common import BENCHMARKS
    from repro.kernels import ref
    from repro.solvers import stencil
    from repro.dist.mesh import make_mesh

    mesh = make_mesh((4,), ("data",))
    out = {{}}
    for name, spec in BENCHMARKS.items():
        if spec.ndim != {ndim}:
            continue
        shape = (64, 128) if spec.ndim == 2 else (32, 12, 16)
        shard = shape[0] // 4
        x = jax.random.normal(jax.random.key(0), shape, jnp.float32)
        base = stencil.run_distributed(x, spec, 5, mesh, fuse_steps=1)
        oracle_err = float(jnp.abs(base - ref.stencil_run(x, spec, 5)).max())
        rows = {{"oracle_err": oracle_err}}
        for t in (2, 4):
            if spec.radius * t > shard:
                try:
                    stencil.run_distributed(x, spec, 5, mesh, fuse_steps=t)
                    rows[str(t)] = "missing ValueError"
                except ValueError:
                    rows[str(t)] = "infeasible"
                continue
            got = stencil.run_distributed(x, spec, 5, mesh, fuse_steps=t)
            rows[str(t)] = float(jnp.abs(got - base).max())
        out[name] = rows
    print(json.dumps(out))
"""


@pytest.mark.parametrize("ndim", [2, 3])
def test_distributed_fused_matches_per_step(ndim, dist_run):
    """fuse_steps in {2, 4} vs per-step exchange over every spec: <= 2 ulp
    (the windows compile to differently-shaped XLA programs; see DESIGN.md
    §4), and a clean ValueError when the fused halo outgrows the shard."""
    res = dist_run(_DIST_FUSED.format(ndim=ndim), n_dev=8, timeout=600)
    specs = {n for n, s in BENCHMARKS.items() if s.ndim == ndim}
    assert set(res) == specs
    for name, rows in res.items():
        assert rows["oracle_err"] < 1e-5, (name, rows)
        for t in ("2", "4"):
            if rows[t] == "infeasible":
                continue
            assert isinstance(rows[t], float) and rows[t] <= 5e-7, (name, rows)


def test_distributed_fused_collective_count(dist_run):
    """The tentpole guarantee: fuse_steps=t issues exactly ceil(steps/t)
    halo exchanges (2 ppermutes each), counted in the traced jaxpr with
    scan trip counts multiplied through."""
    res = dist_run(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.kernels.common import get_spec
        from repro.solvers import stencil
        from repro.dist.mesh import make_mesh

        def count_ppermute(jx, mult=1):
            n = 0
            for eqn in jx.eqns:
                if eqn.primitive.name == "ppermute":
                    n += mult
                m = (mult * eqn.params["length"]
                     if eqn.primitive.name == "scan" else mult)
                for v in eqn.params.values():
                    for s in (v if isinstance(v, (tuple, list)) else (v,)):
                        inner = getattr(s, "jaxpr", s)
                        if hasattr(inner, "eqns"):
                            n += count_ppermute(inner, m)
            return n

        mesh = make_mesh((4,), ("data",))
        spec = get_spec("2d5pt")
        x = jnp.zeros((64, 128), jnp.float32)
        out = {}
        for t in (1, 2, 4):
            jx = jax.make_jaxpr(lambda x: stencil.run_distributed(
                x, spec, 7, mesh, fuse_steps=t))(x)
            out[str(t)] = count_ppermute(jx.jaxpr)
        print(json.dumps(out))
    """), n_dev=8, timeout=600)
    # 7 steps: t=1 -> 7 exchanges, t=2 -> 4 (2+2+2+1), t=4 -> 2 (4+3);
    # each exchange is a fwd+bwd ppermute pair.
    assert res == {"1": 14, "2": 8, "4": 4}


def test_distributed_cg_fused_reductions(dist_run):
    """Pipelined CG: ONE psum per iteration (vs two), matching textbook CG
    even past convergence (banded_4k reaches machine-zero residual well
    before iteration 25 — the regime where an unguarded recurrence
    explodes; see solvers/cg.py)."""
    res = dist_run("""
        import json, jax, jax.numpy as jnp
        from repro.solvers import cg
        from repro.kernels import ref
        from repro.dist.mesh import make_mesh

        def count_psum(jx, mult=1):
            n = 0
            for eqn in jx.eqns:
                if eqn.primitive.name == "psum":
                    n += mult
                m = (mult * eqn.params["length"]
                     if eqn.primitive.name == "scan" else mult)
                for v in eqn.params.values():
                    for s in (v if isinstance(v, (tuple, list)) else (v,)):
                        inner = getattr(s, "jaxpr", s)
                        if hasattr(inner, "eqns"):
                            n += count_psum(inner, m)
            return n

        mesh = make_mesh((8,), ("data",))
        out = {}
        for ds, iters in (("banded_4k", 25), ("poisson_64", 25)):
            data, cols = cg.load_dataset(ds)
            b = jax.random.normal(jax.random.key(1), (data.shape[0],),
                                  jnp.float32)
            x_ref, rr_ref = ref.cg_run(data, cols, b, iters)
            x_f, rr_f = cg.run_distributed(data, cols, b, iters, mesh,
                                           fuse_reductions=True)
            scale = float(jnp.abs(x_ref).max())
            out[ds] = {"rel_err": float(jnp.abs(x_f - x_ref).max()) / scale,
                       "rr": float(rr_f), "rr_ref": float(rr_ref)}
        data, cols = cg.load_dataset("poisson_64")
        b = jnp.ones((data.shape[0],))
        for fused, key in ((True, "fused"), (False, "textbook")):
            jx = jax.make_jaxpr(lambda b: cg.run_distributed(
                data, cols, b, 5, mesh, fuse_reductions=fused))(b)
            out[key + "_psums"] = count_psum(jx.jaxpr)
        print(json.dumps(out))
    """, n_dev=8, timeout=600)
    assert res["fused_psums"] == 5          # one chunked sync per iteration
    assert res["textbook_psums"] == 10      # two dependent syncs
    for ds in ("banded_4k", "poisson_64"):
        assert res[ds]["rel_err"] < 1e-4, res[ds]
        assert abs(res[ds]["rr"] - res[ds]["rr_ref"]) <= \
            1e-3 * (res[ds]["rr_ref"] + 1e-12), res[ds]


# -- the Krylov family across tiers (exec/krylov.py, DESIGN.md §10) -------------

_KRYLOV_TIERS = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.exec import BiCGStabProblem, GMRESProblem, Plan, execute
    from repro.exec.krylov import cg_sstep_distributed
    from repro.kernels import ref
    from repro.solvers import cg as cgs
    from repro.dist.mesh import make_mesh

    mesh = make_mesh((8,), ("data",))
    data, cols = cgs.load_dataset("banded_4k")
    b = jax.random.normal(jax.random.key(1), (data.shape[0],), jnp.float32)
    out = {}

    def rel(x, x_ref):
        return float(jnp.abs(x - x_ref).max()) / float(jnp.abs(x_ref).max())

    # BiCGStab: loop tier == oracle; resident and distributed
    # (fused + textbook reduction schedules) track it.
    iters = 20
    prob = BiCGStabProblem.from_ell(data, cols, b, iters)
    x_o, rr_o = ref.bicgstab_run(data, cols, b, iters)
    rows = {"rr_ref": float(rr_o)}
    x_l, _ = execute(prob, Plan(tier="device_loop"))
    rows["loop"] = float(jnp.abs(x_l - x_o).max())
    x_r, _ = execute(prob, Plan(tier="resident", policy="MIX"))
    rows["resident"] = rel(x_r, x_o)
    for fused, key in ((True, "dist_fused"), (False, "dist_textbook")):
        x_d, rr_d = execute(prob, Plan(tier="distributed",
                                       fuse_reductions=fused), mesh=mesh)
        rows[key] = rel(x_d, x_o)
        rows[key + "_rr"] = float(rr_d)
    out["bicgstab"] = rows

    # GMRES(m): loop == oracle; resident kernel and distributed track it.
    cycles, m = 2, 8
    gprob = GMRESProblem.from_ell(data, cols, b, cycles, m=m)
    xg_o, rrg_o = ref.gmres_run(data, cols, b, cycles, m)
    grows = {"rr_ref": float(rrg_o)}
    xg_l, _ = execute(gprob, Plan(tier="device_loop"))
    grows["loop"] = float(jnp.abs(xg_l - xg_o).max())
    xg_r, _ = execute(gprob, Plan(tier="resident"))
    grows["resident"] = rel(xg_r, xg_o)
    xg_d, _ = execute(gprob, Plan(tier="distributed"), mesh=mesh)
    grows["dist"] = rel(xg_d, xg_o)
    out["gmres"] = grows

    # s-step CG vs standard CG at matched cadence (non-dividing tail).
    x_c, rr_c = ref.cg_run(data, cols, b, 10)
    x_s, rr_s = cg_sstep_distributed(data, cols, b, 10, mesh, s=4)
    out["sstep"] = {"rel": rel(x_s, x_c), "rr": float(rr_s),
                    "rr_ref": float(rr_c), "bb": float(jnp.vdot(b, b))}
    print(json.dumps(out))
"""


def test_krylov_tier_sweep(dist_run):
    """BiCGStab and GMRES(m) across loop / resident / distributed tiers
    on a real registry operator, all against the jnp oracles; s-step CG
    against standard CG at matched total iteration count."""
    res = dist_run(_KRYLOV_TIERS, n_dev=8, timeout=600)
    bi = res["bicgstab"]
    assert bi["loop"] == 0.0                    # same graph, same order
    assert bi["resident"] < 1e-4, bi
    for key in ("dist_fused", "dist_textbook"):
        assert bi[key] < 1e-4, bi
        assert abs(bi[key + "_rr"] - bi["rr_ref"]) <= \
            1e-3 * (bi["rr_ref"] + 1e-12), bi
    gm = res["gmres"]
    assert gm["loop"] < 1e-6, gm                # lstsq: jit vs eager ulps
    assert gm["resident"] < 1e-4, gm
    assert gm["dist"] < 1e-4, gm
    ss = res["sstep"]
    assert ss["rel"] < 1e-3, ss
    # both residuals sit at the f32 convergence floor; the monomial basis
    # stagnates a few ulps higher than textbook CG, so compare both to
    # the initial residual rather than to each other
    assert ss["rr"] <= 1e-8 * ss["bb"], ss
    assert ss["rr_ref"] <= 1e-8 * ss["bb"], ss


def test_krylov_collective_counts(dist_run):
    """The communication contracts, counted in the traced jaxprs with
    scan trip counts multiplied through:

      * pipelined BiCGStab: THREE psums per iteration (rho, rhat.v, the
        stacked stabilization dots) vs FIVE textbook;
      * GMRES(m): 3m+2 psums per restart cycle;
      * s-step CG: ONE psum per s iterations — ceil(iters/s) total, the
        tentpole guarantee.
    """
    res = dist_run("""
        import json, jax, jax.numpy as jnp
        from repro.exec.krylov import (bicgstab_distributed,
                                       cg_sstep_distributed,
                                       gmres_distributed)
        from repro.solvers import cg as cgs
        from repro.dist.mesh import make_mesh

        def count_psum(jx, mult=1):
            n = 0
            for eqn in jx.eqns:
                if eqn.primitive.name == "psum":
                    n += mult
                m = (mult * eqn.params["length"]
                     if eqn.primitive.name == "scan" else mult)
                for v in eqn.params.values():
                    for s in (v if isinstance(v, (tuple, list)) else (v,)):
                        inner = getattr(s, "jaxpr", s)
                        if hasattr(inner, "eqns"):
                            n += count_psum(inner, m)
            return n

        mesh = make_mesh((8,), ("data",))
        data, cols = cgs.load_dataset("poisson_64")
        b = jnp.ones((data.shape[0],))
        out = {}
        for fused, key in ((True, "bicgstab_fused"),
                           (False, "bicgstab_textbook")):
            jx = jax.make_jaxpr(lambda b: bicgstab_distributed(
                data, cols, b, 5, mesh, fuse_reductions=fused))(b)
            out[key] = count_psum(jx.jaxpr)
        jx = jax.make_jaxpr(lambda b: gmres_distributed(
            data, cols, b, 2, 8, mesh))(b)
        out["gmres"] = count_psum(jx.jaxpr)
        for iters, s in ((12, 4), (6, 3), (10, 4), (5, 1)):
            jx = jax.make_jaxpr(lambda b: cg_sstep_distributed(
                data, cols, b, iters, mesh, s=s))(b)
            out[f"sstep_{iters}_{s}"] = count_psum(jx.jaxpr)
        print(json.dumps(out))
    """, n_dev=8, timeout=600)
    assert res["bicgstab_fused"] == 15      # 3 per iteration x 5
    assert res["bicgstab_textbook"] == 25   # 5 per iteration x 5
    assert res["gmres"] == 52               # (3*8 + 2) per cycle x 2
    # ONE Gram-matrix psum per s iterations, ceil on the tail:
    assert res["sstep_12_4"] == 3
    assert res["sstep_6_3"] == 2
    assert res["sstep_10_4"] == 3           # 4+4+2
    assert res["sstep_5_1"] == 5            # s=1 degenerates to 1/iter
