"""End-to-end behaviour of the paper's system: solvers under the PERKS
execution model, caching policies, HLO cost accounting, serving engine."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hlo_costs
from repro.core.cache_policy import cg_arrays
from repro.core.hardware import TPU_V5E
from repro.exec import CGProblem, Plan, StencilProblem, execute
from repro.exec.planner import cg_policy_from_arrays
from repro.kernels import ref
from repro.kernels.common import get_spec
from repro.kernels.ops import load_dataset
from repro.kernels.stencil3d import plan_resident_planes

KEY = jax.random.key(0)


# -- stencil system ----------------------------------------------------------

def test_stencil_execution_tiers_identical():
    spec = get_spec("2d13pt")
    x = jax.random.normal(KEY, (64, 128), jnp.float32)
    problem = StencilProblem(x, spec, 5)
    a = execute(problem, Plan(tier="host_loop"))
    b = execute(problem, Plan(tier="device_loop"))
    c = execute(problem, Plan(tier="resident", cached_rows=32, sub_rows=16))
    want = ref.stencil_run(x, spec, 5)
    for got in (a, b, c):
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_stencil_cache_plan_reporting():
    spec = get_spec("2d5pt")
    rows = plan_resident_planes((4096, 4096), 4, spec, chip=TPU_V5E)
    assert 0 < rows <= 4096
    # small domain fully cached
    assert plan_resident_planes((1024, 1024), 4, spec, chip=TPU_V5E) == 1024


# -- CG system ----------------------------------------------------------------

def test_cg_tiers_agree_and_converge():
    data, cols = load_dataset("poisson_64")
    b = jax.random.normal(KEY, (data.shape[0],), jnp.float32)
    problem = CGProblem.from_ell(data, cols, b, 25)
    x_h, rr_h = execute(problem, Plan(tier="host_loop"))
    x_d, rr_d = execute(problem, Plan(tier="device_loop"))
    x_f, rr_f = execute(problem, Plan(tier="resident", policy="MIX",
                                      block_rows=256))
    np.testing.assert_allclose(x_h, x_d, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(x_h, x_f, rtol=1e-3, atol=1e-4)
    assert float(rr_d) < float(jnp.vdot(b, b))


def test_cg_early_stop_on_convergence():
    data, cols = load_dataset("poisson_64")
    b = jax.random.normal(KEY, (data.shape[0],), jnp.float32)
    x, rr = execute(CGProblem.from_ell(data, cols, b, 500, tol=1e-10),
                    Plan(tier="device_loop", sync_every=25))
    assert float(rr) < 1e-10 * float(jnp.vdot(b, b)) * 10


def test_cg_policy_planner():
    def policy(n, nnz):
        budget = int(TPU_V5E.onchip_bytes * 0.9)
        return cg_policy_from_arrays(cg_arrays(n, nnz, 4), budget)

    # small problem: everything fits -> MIX
    assert policy(10_000, 50_000)["policy"] == "MIX"
    # huge problem: vectors alone exceed VMEM -> IMP
    assert policy(10**9, 10**10)["policy"] == "IMP"
    # vectors fit, matrix does not fit at all -> policy still caches vectors
    mid = policy(10**6, 3 * 10**8)
    assert mid["vector_fraction"] == 1.0


# -- HLO cost accounting --------------------------------------------------------

def test_hlo_costs_exact_on_matmul():
    f = jax.jit(lambda a, b: a @ b)
    c = f.lower(jnp.zeros((512, 256)), jnp.zeros((256, 128))).compile()
    hc = hlo_costs.analyze(c.as_text())
    assert abs(hc.flops - 2 * 512 * 256 * 128) / hc.flops < 1e-6


def test_hlo_costs_scan_trip_counts():
    def step(c, _):
        return c @ jnp.eye(128), None
    g = jax.jit(lambda c: jax.lax.scan(step, c, None, length=12))
    c = g.lower(jnp.zeros((128, 128))).compile()
    hc = hlo_costs.analyze(c.as_text())
    want = 12 * 2 * 128 ** 3
    assert abs(hc.flops - want) / want < 1e-6
    assert hc.flops_scale > 10  # raw count misses the trip count


def test_hlo_costs_collectives(dist_run):
    """Collectives inside scan bodies are multiplied by trip count."""
    res = dist_run("""
        import json, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import hlo_costs
        from repro.dist.mesh import make_mesh
        mesh = make_mesh((4,), ("d",))
        sh = NamedSharding(mesh, P("d", None))
        def step(c, _):
            s = c.sum()                      # all-reduce per step
            return c + s, None
        f = jax.jit(lambda c: jax.lax.scan(step, c, None, length=10)[0],
                    in_shardings=sh, out_shardings=sh)
        comp = f.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32,
                                            sharding=sh)).compile()
        hc = hlo_costs.analyze(comp.as_text())
        print(json.dumps({"ar": hc.coll_count.get("all-reduce", 0)}))
    """, n_dev=4, timeout=240)
    assert res["ar"] >= 10  # one per scan step, trip-multiplied


# -- serving engine --------------------------------------------------------------

def test_engine_persistent_matches_host_loop():
    from repro.configs.registry import get_smoke_config
    from repro.models.lm import Model
    from repro.runtime.server import Engine, Request, ServeConfig

    cfg = get_smoke_config("h2o-danube-1.8b")
    model = Model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 16, dtype=np.int32)
               for _ in range(3)]

    def serve(persistent):
        eng = Engine(model, params, ServeConfig(max_batch=4,
                                                persistent=persistent))
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=8))
        toks, stats = eng.run_batch()
        return toks, stats

    t_perks, s_perks = serve(True)
    t_base, s_base = serve(False)
    np.testing.assert_array_equal(t_perks, t_base)
    assert s_perks["mode"] == "persistent"
