"""The distributed stencil tier as the planner reaches it: HBM decides
when a field leaves one chip (``plan(..., mesh=)``), the sharded program
matches the plain reference, and the executor counts the halo bytes it
moves. Multi-device cases run in one child with four CPU devices
(``dist_run``); the planner reads only the mesh's shape, so its cases use
an ``AbstractMesh``."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from repro.core.hardware import CPU_INTERPRET, TPU_V5E
from repro.exec import StencilProblem, plan, plan_candidates
from repro.kernels.common import get_spec

MESH4 = AbstractMesh((4,), ("data",))

#: 64x256 f32 is 64 KiB: a one-chip plan needs at least 192 KiB (input,
#: output and the resident kernel's temporary), a shard of four about 80
#: KiB; 128 KiB of HBM holds the second and not the first.
SMALL_HBM = dataclasses.replace(CPU_INTERPRET, hbm_bytes=128 * 1024)


def _problem(shape, steps=8):
    return StencilProblem(jax.ShapeDtypeStruct(shape, jnp.float32),
                          get_spec("2d5pt"), steps)


def test_field_over_one_chip_plans_distributed():
    cands = plan_candidates(_problem((64, 256)), chip=SMALL_HBM, mesh=MESH4)
    assert cands and all(c.tier == "distributed" for c in cands)
    assert sorted(c.fuse_steps for c in cands) == [1, 2, 4]
    assert plan(_problem((64, 256)), chip=SMALL_HBM,
                mesh=MESH4).tier == "distributed"
    # a field one chip holds keeps its one-chip plan, mesh or not
    assert plan(_problem((16, 256)), chip=SMALL_HBM,
                mesh=MESH4).tier == "resident"


def test_field_over_one_chip_without_mesh_names_footprint_and_limit():
    with pytest.raises(ValueError, match=r"196,608 bytes \(0\.00 GiB\) of HBM "
                       r"on one chip.*has 131,072 bytes \(0\.00 GiB\); "
                       r"pass mesh= to shard it"):
        plan(_problem((64, 256)), chip=SMALL_HBM)


def test_field_over_every_shard_names_the_shard_footprint():
    p = _problem((65536, 262144), steps=64)      # 64 GiB a field
    with pytest.raises(ValueError, match=r"\(64\.00 GiB\) of HBM on each of 4 "
                       r"chips.*tpu_v5e has .* \(15\.75 GiB\)"):
        plan(p, chip=dataclasses.replace(TPU_V5E, hbm_bytes=15.75 * 2**30),
             mesh=MESH4)


def test_cell_field_plans_distributed_on_a_v5e():
    """65536x32768 f32 (8 GiB): no one-chip plan fits a v5e's usable
    15.75 GiB, a 16384-row shard does."""
    v5e = dataclasses.replace(TPU_V5E, hbm_bytes=15.75 * 2**30)
    p = _problem((65536, 32768), steps=64)
    assert plan(p, chip=v5e, mesh=MESH4).tier == "distributed"
    with pytest.raises(ValueError, match=r"\(24\.00 GiB\) of HBM on one chip"):
        plan(p, chip=v5e)


@pytest.mark.parametrize("shape,want", [
    ((16384, 8192), dict(cached_rows=1176, sub_rows=32)),   # stream cell
    ((3072, 3072), dict(cached_rows=3072, sub_rows=80)),    # resident cell
])
def test_one_chip_cells_keep_their_plans(shape, want):
    """The one-chip benchmark fields get the deep resident plan they got
    before HBM was checked, for the published and the usable HBM alike."""
    p = _problem(shape, steps=256)
    usable = dataclasses.replace(TPU_V5E, hbm_bytes=15.75 * 2**30)
    got = plan(p, chip=usable, mesh=MESH4)
    assert got == plan(p, chip="tpu_v5e")
    assert (got.tier, got.schedule, got.fuse_steps) == ("resident", "deep",
                                                        32)
    assert dict(cached_rows=got.cached_rows, sub_rows=got.sub_rows) == want


_CHILD = """
import dataclasses, importlib.util, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import obs
from repro.core.hardware import CPU_INTERPRET
from repro.exec import StencilProblem, execute, plan_candidates
from repro.exec.adapters import fusion_schedule
from repro.kernels import ref
from repro.kernels.common import get_spec

chip = dataclasses.replace(CPU_INTERPRET, hbm_bytes=128 * 1024)
mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
sh = NamedSharding(mesh, P("data", None))
spec = get_spec("2d5pt")
x = jax.device_put(jax.random.normal(jax.random.key(3), (64, 256),
                                     jnp.float32), sh)
out = {"err": {}, "vs_per_step": {}, "halo": {}}
for steps in (8, 7):
    prob = StencilProblem(x, spec, steps)
    want = ref.stencil_run(x, spec, steps)
    base = None
    for p in sorted(plan_candidates(prob, chip=chip, mesh=mesh),
                    key=lambda p: p.fuse_steps):
        reg, tr = obs.MetricsRegistry(), obs.Tracer()
        with obs.use_metrics(reg), obs.use_tracer(tr):
            got = execute(prob, p, mesh=mesh)
        key = f"{steps}/{p.tier}/{p.fuse_steps}"
        out["err"][key] = float(jnp.abs(got - want).max())
        base = got if base is None else base
        out["vs_per_step"][key] = float(jnp.abs(got - base).max())
        span = tr.by_cat("dispatch")[-1]
        rows = sum(n * 2 * 3 * t for n, t in fusion_schedule(steps,
                                                           p.fuse_steps))
        out["halo"][key] = {
            "counted": reg.value("executor_halo_bytes_total",
                                 tier="distributed"),
            "want": rows * 256 * 4,
            "args": {k: v for k, v in dict(span.args).items()
                     if k in ("shards", "shard_rows", "halo_rows")},
            "halo_rows": p.fuse_steps}

path = "__BENCH__/configs/jacobi2d5pt-4chip.py"
s = importlib.util.spec_from_file_location("reference_4chip", path)
mod = importlib.util.module_from_spec(s)
s.loader.exec_module(mod)
y = jax.random.uniform(jax.random.key(4), (64, 256), jnp.float32)
whole = mod.run(y, steps=9)
banded = mod.run_banded(jax.device_put(y, sh), steps=9, bands=4)
one_device = mod.run_banded(y, steps=9, bands=4)
out["banded"] = {
    "sharded_equal": bool(np.array_equal(np.asarray(banded),
                                         np.asarray(whole))),
    "sharding_kept": banded.sharding.is_equivalent_to(sh, 2),
    "one_device_equal": bool(np.array_equal(np.asarray(one_device),
                                            np.asarray(whole)))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child(dist_run):
    from conftest import REPO
    return dist_run(_CHILD.replace("__BENCH__", str(REPO / "bench")), n_dev=4)


@pytest.mark.parametrize("steps", [8, 7])       # 7: a tail chunk for t=2, 4
@pytest.mark.parametrize("fuse", [1, 2, 4])
def test_planned_distributed_matches_reference(child, steps, fuse):
    key = f"{steps}/distributed/{fuse}"
    assert child["err"][key] < 1e-6, child["err"]
    # the fused windows against the per-step exchange: <= 2 ulp
    assert child["vs_per_step"][key] <= 5e-7, child["vs_per_step"]


@pytest.mark.parametrize("steps", [8, 7])
@pytest.mark.parametrize("fuse", [1, 2, 4])
def test_halo_bytes_counted_from_the_plan(child, steps, fuse):
    h = child["halo"][f"{steps}/distributed/{fuse}"]
    assert h["counted"] == h["want"] > 0
    assert h["args"] == {"shards": 4, "shard_rows": 16, "halo_rows": fuse}


def test_reference_run_banded_equals_whole_field_run(child):
    assert child["banded"] == {"sharded_equal": True, "sharding_kept": True,
                               "one_device_equal": True}
