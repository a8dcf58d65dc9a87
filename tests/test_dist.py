"""Distributed tests — spawn subprocesses with fake multi-device CPU so the
main test process keeps seeing exactly one device (assignment requirement).
The runner lives in conftest.py (``dist_run`` fixture); mesh construction
goes through ``repro.dist.mesh.make_mesh`` (Auto axis types on every JAX
version).
"""


def test_distributed_stencil_matches_single(dist_run):
    res = dist_run("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.kernels.common import get_spec
        from repro.kernels import ref
        from repro.exec import Plan, StencilProblem, execute
        from repro.dist.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        spec = get_spec("2ds9pt")
        x = jax.random.normal(jax.random.key(0), (64, 128), jnp.float32)
        got = execute(StencilProblem(x, spec, 7),
                      Plan(tier="distributed", shard_axis="data"), mesh=mesh)
        want = ref.stencil_run(x, spec, 7)
        print(json.dumps({"err": float(jnp.abs(got - want).max())}))
    """)
    assert res["err"] < 1e-5


def test_distributed_cg_matches_single(dist_run):
    res = dist_run("""
        import json, jax, jax.numpy as jnp
        from repro.exec import CGProblem, Plan, execute
        from repro.kernels import ref
        from repro.kernels.ops import load_dataset
        from repro.dist.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        data, cols = load_dataset("poisson_64")
        b = jax.random.normal(jax.random.key(1), (data.shape[0],), jnp.float32)
        x_d, rr_d = execute(CGProblem.from_ell(data, cols, b, 15),
                            Plan(tier="distributed", shard_axis="data"),
                            mesh=mesh)
        x_s, rr_s = ref.cg_run(data, cols, b, 15)
        print(json.dumps({
            "err": float(jnp.abs(x_d - x_s).max()),
            "rr_rel": float(abs(rr_d - rr_s) / rr_s)}))
    """)
    assert res["err"] < 1e-3 and res["rr_rel"] < 1e-3


def test_distributed_cg_nnz_partition(dist_run):
    """nnz-balanced sharding (repro.sparse.partition) is algebraically
    invisible: same solution as equal-rows on an irregular matrix whose
    naive shards would be badly imbalanced."""
    res = dist_run("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.exec import CGProblem, Plan, execute
        from repro.kernels import ref
        from repro.dist.mesh import make_mesh
        from repro.sparse import (balance_report, load_matrix,
                                  nnz_balanced_partition)
        mesh = make_mesh((8,), ("data",))
        csr = load_matrix("graph_powerlaw_8k")
        ell = csr.to_ell()
        data, cols = jnp.asarray(ell.data), jnp.asarray(ell.cols)
        b = jax.random.normal(jax.random.key(1), (csr.shape[0],), jnp.float32)
        x_n, rr_n = execute(CGProblem.from_ell(data, cols, b, 8),
                            Plan(tier="distributed", shard_axis="data",
                                 partition="nnz"), mesh=mesh)
        x_s, rr_s = ref.cg_run(data, cols, b, 8)
        bounds = nnz_balanced_partition(csr.row_nnz, 8)
        eq = np.linspace(0, csr.shape[0], 9).astype(np.int64)
        print(json.dumps({
            "err": float(jnp.abs(x_n - x_s).max() / jnp.abs(x_s).max()),
            "rr_rel": float(abs(rr_n - rr_s) / rr_s),
            "imb_nnz": balance_report(bounds, csr.row_nnz)["imbalance"],
            "imb_rows": balance_report(eq, csr.row_nnz)["imbalance"]}))
    """, timeout=600)
    assert res["err"] < 1e-3 and res["rr_rel"] < 1e-3
    assert res["imb_nnz"] < 1.1 < res["imb_rows"]


def test_sharded_flash_decode_matches_ref(dist_run):
    res = dist_run("""
        import json, jax, jax.numpy as jnp
        from repro.dist.collectives import sharded_decode_attention
        from repro.dist.mesh import make_mesh
        from repro.kernels import ref
        mesh = make_mesh((8,), ("model",))
        B, Hq, Hkv, S, D = 2, 8, 2, 256, 32
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
        length = jnp.array([200, 256], jnp.int32)
        with mesh:
            got = sharded_decode_attention(q, k, v, mesh=mesh,
                                           seq_axis="model", length=length)
        want = ref.decode_attention(q, k, v, length=length)
        print(json.dumps({"err": float(jnp.abs(got - want).max())}))
    """)
    assert res["err"] < 1e-4


def test_pipeline_parallel_matches_sequential(dist_run):
    res = dist_run("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import pipeline_apply, bubble_fraction
        from repro.dist.mesh import make_mesh
        mesh = make_mesh((4,), ("stage",))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        key = jax.random.key(0)
        w = jax.random.normal(key, (n_stages, d, d)) / jnp.sqrt(d)
        xs = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
        stage_fn = lambda p, h: jnp.tanh(h @ p["w"])
        with mesh:
            got = pipeline_apply(stage_fn, {"w": w}, xs, mesh=mesh,
                                 stage_axis="stage")
        want = xs
        for s in range(n_stages):
            want = jnp.tanh(want @ w[s])
        print(json.dumps({
            "err": float(jnp.abs(got - want).max()),
            "bubble": bubble_fraction(n_micro, n_stages)}))
    """)
    assert res["err"] < 1e-5
    assert abs(res["bubble"] - 3 / 11) < 1e-9


def test_moe_ep_matches_single_device(dist_run):
    """Expert-parallel shard_map MoE == single-device routing."""
    res = dist_run("""
        import json, jax, jax.numpy as jnp
        from repro.configs.registry import get_smoke_config
        from repro.dist import sharding as shd
        from repro.dist.mesh import make_mesh
        from repro.models import moe as moe_lib
        from repro.models.lm import Model
        cfg = get_smoke_config("qwen3-moe-235b-a22b")
        m = Model(cfg)
        params = m.init(jax.random.key(0))
        lp = jax.tree.map(lambda p: p[0], params["layers"])
        x = jax.random.normal(jax.random.key(2), (2, 16, cfg.d_model),
                              jnp.bfloat16)
        y_single, aux_single = moe_lib.moe_apply(lp["mlp"], cfg, x)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = shd.make_rules(mesh)
        with mesh, shd.use_rules(rules):
            y_ep, aux_ep = jax.jit(
                lambda p, x: moe_lib.moe_apply(p, cfg, x))(lp["mlp"], x)
        per_tok = jnp.abs(y_ep.astype(jnp.float32)
                          - y_single.astype(jnp.float32)).max(-1)
        frac_bad = float((per_tok > 0.1).mean())
        med = float(jnp.median(per_tok))
        print(json.dumps({
            "frac_bad": frac_bad, "median": med,
            "aux_rel": float(abs(aux_ep - aux_single) / (abs(aux_single) + 1e-9))}))
    """, n_dev=8)
    # per-shard capacity (and bf16 router near-ties) can drop/route a few
    # tokens differently between the single-device and EP paths; demand
    # that almost all tokens agree and the rest is bounded drop noise
    assert res["frac_bad"] <= 0.2, res
    assert res["median"] < 0.05, res
    assert res["aux_rel"] < 0.25, res


def test_elastic_checkpoint_across_mesh_sizes(tmp_path, dist_run):
    """Save on 8 devices, restore on 4 — logical checkpoint reshards."""
    code = f"""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import checkpoint as ckpt
        from repro.dist.mesh import make_mesh
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh, P("data", None)))
        tree = {{"w": w}}
        import pathlib
        d = pathlib.Path({str(tmp_path)!r})
        if n == 8:
            ckpt.save(d, 1, tree)
            print(json.dumps({{"saved": True}}))
        else:
            got, _ = ckpt.restore(ckpt.find_latest(d), tree,
                                  shardings={{"w": NamedSharding(mesh, P("data", None))}})
            ok = bool((np.asarray(got["w"]) ==
                       np.arange(64.0).reshape(8, 8)).all())
            print(json.dumps({{"ok": ok,
                               "nshards": len(got["w"].sharding.device_set)}}))
    """
    assert dist_run(code, n_dev=8)["saved"]
    res = dist_run(code, n_dev=4)
    assert res["ok"] and res["nshards"] == 4
