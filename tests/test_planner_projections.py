"""Golden gate on the planner's *deterministic* projections.

The perf model (``core.perf_model``, paper Eqs. 5-11, generalized by the
batched planner) is pure arithmetic over static shapes and chip specs, so
it is bit-reproducible on any machine. This projects the planner's
winning time for a fixed portfolio of problems (stencil families at
production shapes, CG, BiCGStab and GMRES at one operator size or two,
decode and the SSD scan, each at batch 1 and 8) on ``ShapeDtypeStruct``
operands, and compares each with ``data/planner_projections.json``.

A case fails when its projection regresses more than ``TOLERANCE`` (5%)
or disappears; the portfolio test fails when an entry is added that the
baseline lacks. Improvements pass. A change that moves a projection on
purpose refreshes the baseline in the same commit:

    PYTHONPATH=src python tests/test_planner_projections.py > tests/data/planner_projections.json
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.exec import (
    BiCGStabProblem,
    CGProblem,
    DecodeAttentionProblem,
    GMRESProblem,
    SSMScanProblem,
    StencilProblem,
    plan,
)
from repro.kernels.common import get_spec

BASELINE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "planner_projections.json"
)

# allowed slowdown before a case fails
TOLERANCE = 0.05

# planner-visible portfolio: (family, shape, steps) x batch, projected on
# ShapeDtypeStruct domains — no device memory is ever allocated
STENCILS = (
    ("2d5pt", (4096, 4096), 1000),
    ("2d25pt", (2048, 2048), 500),
    ("3d7pt", (256, 256, 128), 200),
    ("3d27pt", (128, 128, 128), 200),
)
CGS = (
    (65_536, 8, 200),
    (1_048_576, 16, 100),
)
# Krylov family (DESIGN.md §10): one BiCGStab and one GMRES(m) portfolio
# entry each, projected on abstract operands like the CG rows
BICGSTAB = ((65_536, 8, 100),)
GMRES = ((65_536, 8, 16, 6),)  # (n, k, m, cycles)
# ML problems (DESIGN.md §13): decode projected on abstract cache/params
# specs per smoke arch, SSD scan on abstract streams
DECODES = (("qwen2-0.5b", 4, 64, 31), ("mamba2-780m", 4, 64, 31))
SSMS = ((4096, 8, 16, 32, 128),)  # (T, H, P, N, chunk)
BATCHES = (1, 8)


@functools.lru_cache(maxsize=1)
def current_projections() -> dict[str, float]:
    out: dict[str, float] = {}
    for name, shape, steps in STENCILS:
        spec = get_spec(name)
        x = jax.ShapeDtypeStruct(shape, jnp.float32)
        problem = StencilProblem(x, spec, steps)
        dims = "x".join(map(str, shape))
        for b in BATCHES:
            chosen = plan(problem, batch=b)
            out[f"stencil_{name}_{dims}_n{steps}_b{b}"] = chosen.predicted_s
    for n, k, iters in CGS:
        problem = CGProblem(
            b=jax.ShapeDtypeStruct((n,), jnp.float32),
            n_steps=iters,
            data=jax.ShapeDtypeStruct((n, k), jnp.float32),
            cols=None,
        )
        for b in BATCHES:
            chosen = plan(problem, batch=b)
            out[f"cg_n{n}_k{k}_i{iters}_b{b}"] = chosen.predicted_s
    for n, k, iters in BICGSTAB:
        problem = BiCGStabProblem(
            b=jax.ShapeDtypeStruct((n,), jnp.float32),
            n_steps=iters,
            data=jax.ShapeDtypeStruct((n, k), jnp.float32),
            cols=None,
        )
        for b in BATCHES:
            chosen = plan(problem, batch=b)
            out[f"bicgstab_n{n}_k{k}_i{iters}_b{b}"] = chosen.predicted_s
    for n, k, m, cycles in GMRES:
        problem = GMRESProblem(
            b=jax.ShapeDtypeStruct((n,), jnp.float32),
            n_steps=cycles,
            m=m,
            data=jax.ShapeDtypeStruct((n, k), jnp.float32),
            cols=None,
        )
        for b in BATCHES:
            chosen = plan(problem, batch=b)
            out[f"gmres_n{n}_k{k}_m{m}_c{cycles}_b{b}"] = chosen.predicted_s
    for arch, rows, ctx, steps in DECODES:
        from repro.configs.registry import get_smoke_config
        from repro.models.lm import Model

        model = Model(get_smoke_config(arch))
        problem = DecodeAttentionProblem(
            model=model,
            params=jax.eval_shape(model.init, jax.random.key(0)),
            cache=model.cache_spec(rows, ctx),
            first_tokens=jax.ShapeDtypeStruct((rows,), jnp.int32),
            n_steps=steps,
        )
        for b in BATCHES:
            chosen = plan(problem, batch=b)
            out[f"decode_{arch}_r{rows}_c{ctx}_n{steps}_b{b}"] = chosen.predicted_s
    for t, h, p, n, chunk in SSMS:
        problem = SSMScanProblem(
            x=jax.ShapeDtypeStruct((t, h, p), jnp.float32),
            dt=jax.ShapeDtypeStruct((t, h), jnp.float32),
            a=jax.ShapeDtypeStruct((h,), jnp.float32),
            b=jax.ShapeDtypeStruct((t, n), jnp.float32),
            c=jax.ShapeDtypeStruct((t, n), jnp.float32),
            d=jax.ShapeDtypeStruct((h,), jnp.float32),
            chunk=chunk,
        )
        for b in BATCHES:
            chosen = plan(problem, batch=b)
            out[f"ssm_t{t}_h{h}_p{p}_n{n}_ck{chunk}_b{b}"] = chosen.predicted_s
    return out


with open(BASELINE_PATH) as f:
    BASELINE: dict[str, float] = json.load(f)


@pytest.mark.parametrize("key", sorted(BASELINE))
def test_planner_projection_within_tolerance(key):
    current = current_projections()
    assert key in current, f"{key}: projection disappeared (coverage regression)"
    base, cur = BASELINE[key], current[key]
    assert cur <= base * (1.0 + TOLERANCE), (
        f"{key}: {base:.4e}s -> {cur:.4e}s ({(cur / base - 1.0) * 100.0:+.1f}%)"
    )


def test_planner_projection_portfolio_matches_baseline():
    extra = sorted(set(current_projections()) - set(BASELINE))
    assert not extra, f"not in {BASELINE_PATH}; refresh it: {extra}"


if __name__ == "__main__":
    print(json.dumps(current_projections(), indent=2, sort_keys=True))
