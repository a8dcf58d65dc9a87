#!/usr/bin/env python3
"""Drive the PERKS main path once on a TPU and check every result.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # the distributed tier on four chips,
                                     # against the same problems on one

Everything runs in this one process, through the entry points a user
calls: a Problem, ``plan()`` with the attached chip, ``execute()``; the
``AsyncSolverService``; the LM serving ``Engine``. Data and weights are
made on the device from ``--seed``.

Phases (one chip):
  stencil  2d5pt and 2ds9pt on 8192^2 f32 (4x VMEM, streamed), 2d5pt on a
           VMEM-resident 1024^2, 3d7pt on 256^3; each against the
           ``kernels/ref.py`` oracle run by XLA on the same chip. The
           compiled step must hold a Mosaic kernel (``tpu_custom_call``).
  krylov   CG on the 2D Poisson operator and BiCGStab on the 2D
           convection-diffusion operator, both of a 1024^2 grid (1,048,576
           rows), to a relative residual of 1e-4; the true residual is
           checked in float64 on the host.
  service  8 mixed stencil/CG requests through ``AsyncSolverService``,
           each against the same request solved alone.
  lm       qwen2-0.5b at its published width through ``Engine``: 8
           requests, prompt 128, 32 new tokens; persistent and host-loop
           tokens identical, logits finite.

Phases (``--chips 4``): 2d5pt 8192^2 on the distributed tier (fuse_steps=4)
and CG with fused reductions, each on a 4-device mesh and against the same
problem on one chip; the output must sit on four devices.

Each phase prints its tier, compile time and wall time on lines of their
own. The last stdout line is one JSON object, ``{"ok": true, "device":
{...}}``; a failed phase or check raises before it (non-zero exit). Without
a TPU the script exits 2 and runs nothing. JAX's persistent compilation
cache is on (``repro.core.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: Largest |kernel - oracle| admitted for a stencil run (cells are O(1)).
STENCIL_ATOL = 1e-5
#: Krylov target: ||r_k|| / ||b|| at the solver's own convergence check.
KRYLOV_REL_RES = 1e-4
#: Admitted true residual ||b - A x|| / ||b|| in float64 on the host: the
#: target plus the drift of float32 recurrences from the true residual.
KRYLOV_TRUE_RES = 1e-3
#: Largest max|served - solo| / max|solo| admitted for a served request.
SERVICE_RTOL = 1e-5


def fail(msg: str):
    raise RuntimeError(msg)


def log(phase: str, **kv) -> None:
    for k, v in kv.items():
        print(f"[{phase}] {k}={v}", flush=True)


@contextlib.contextmanager
def timed(phase: str):
    """Print the compile time and the wall time of the block. Compile time
    is the sum of JAX's backend-compile events (XLA and Mosaic); its
    tracing and lowering events nest inside one another, so summing them
    would count a nested jit twice."""
    compile_s = [0.0]

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        wall = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(listen)
    log(phase, compile_s=f"{compile_s[0]:.3f}", wall_s=f"{wall:.3f}")


# -- stencils -----------------------------------------------------------------

def stencil_phase(chip, name: str, side: int, steps: int, seed: int) -> None:
    from repro.exec import StencilProblem, execute, plan
    from repro.kernels import ref
    from repro.kernels.common import get_spec

    spec = get_spec(name)
    shape = (side,) * spec.ndim
    phase = f"stencil {name} {'x'.join(map(str, shape))}"
    x = jax.random.uniform(jax.random.key(seed), shape, jnp.float32)
    p = plan(StencilProblem(x, spec, steps), chip=chip)
    log(phase, tier=p.tier, schedule=p.schedule, fuse_steps=p.fuse_steps,
        cached_rows=p.cached_rows, sub_rows=p.sub_rows)
    if p.tier != "resident":
        fail(f"{phase}: planner chose {p.tier}, not the resident tier")
    with timed(phase):
        step = jax.jit(lambda a: execute(StencilProblem(a, spec, steps), p))
        compiled = step.lower(x).compile()
        if "tpu_custom_call" not in compiled.as_text():
            fail(f"{phase}: the compiled step holds no Mosaic kernel")
        t0 = time.perf_counter()
        y = jax.block_until_ready(compiled(x))
        run_s = time.perf_counter() - t0
    log(phase, run_s=f"{run_s:.3f}")
    oracle = jax.jit(ref.stencil_run, static_argnums=(1, 2))(x, spec, steps)
    err = float(jnp.max(jnp.abs(y - oracle)))
    log(phase, max_abs_err=err, atol=STENCIL_ATOL)
    if not err <= STENCIL_ATOL:
        fail(f"{phase}: max |kernel - oracle| = {err} > {STENCIL_ATOL}")


# -- Krylov -------------------------------------------------------------------

def true_residual(data, cols, b, x) -> float:
    """||b - A x|| / ||b|| in float64 on the host (A in ELL form)."""
    x = np.asarray(x, np.float64)
    ax = (np.asarray(data, np.float64) * x[np.asarray(cols)]).sum(axis=1)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - ax) / np.linalg.norm(b))


def krylov_phase(chip, side: int, seed: int, max_iters: int) -> None:
    from repro.exec import BiCGStabProblem, CGProblem, execute, plan
    from repro.kernels.spmv_ell import poisson2d_ell
    from repro.sparse.generate import convdiff2d

    b = np.random.default_rng(seed).standard_normal(side * side)
    b = b.astype(np.float32)
    cd = convdiff2d(side).to_ell()
    # CG on the SPD Poisson operator; BiCGStab on the repo's nonsymmetric
    # one (in float32 it stagnates near 1e-3 on the Poisson operator and
    # then diverges, on any backend)
    for cls, op, (data, cols) in (
            (CGProblem, "poisson2d", poisson2d_ell(side)),
            (BiCGStabProblem, "convdiff2d", (cd.data, cd.cols))):
        phase = f"krylov {cls.__name__} {op} {side}^2"
        dev = [jnp.asarray(a) for a in (data, cols, b)]
        prob = cls.from_ell(*dev, max_iters, tol=KRYLOV_REL_RES ** 2)
        p = plan(prob, chip=chip)
        log(phase, tier=p.tier, sync_every=p.sync_every)
        with timed(phase):
            x, _ = jax.block_until_ready(execute(prob, p))
        res = true_residual(data, cols, b, x)
        log(phase, true_rel_residual=res, bound=KRYLOV_TRUE_RES)
        if not res <= KRYLOV_TRUE_RES:
            fail(f"{phase}: true residual {res} > {KRYLOV_TRUE_RES}")


# -- service ------------------------------------------------------------------

def service_phase(chip, seed: int, *, stencil_side: int, cg_side: int,
                  chunk: int) -> None:
    from repro.exec import CGProblem, Plan, StencilProblem, execute
    from repro.kernels.common import get_spec
    from repro.kernels.spmv_ell import poisson2d_ell
    from repro.runtime.solver_service import AsyncConfig, AsyncSolverService

    phase = "service"
    data, cols = (jnp.asarray(a) for a in poisson2d_ell(cg_side))
    probs = []
    for i in range(4):
        k = jax.random.key(seed + i)
        probs.append(StencilProblem(
            jax.random.uniform(k, (stencil_side,) * 2, jnp.float32),
            get_spec("2d5pt"), 4 * chunk))
        probs.append(CGProblem.from_ell(
            data, cols, jax.random.normal(k, (cg_side ** 2,), jnp.float32),
            40 * chunk, tol=KRYLOV_REL_RES ** 2))
    eng = AsyncSolverService(AsyncConfig(max_batch=4, chunk_steps=chunk,
                                         chip=chip))
    with timed(phase):
        ids = {eng.submit(p): p for p in probs}
        results = jax.block_until_ready(eng.run_until_idle())
    tiers = sorted({r.plan.tier for r in results.values()})
    log(phase, tier=",".join(tiers), served=len(results))
    if set(results) != set(ids):
        fail(f"{phase}: answered {len(results)} of {len(ids)} requests")
    exact, worst = 0, 0.0
    for rid, prob in ids.items():
        got = jax.tree.leaves(results[rid].result)[0]
        want = jax.tree.leaves(
            execute(prob, Plan(tier="device_loop", sync_every=chunk)))[0]
        got, want = np.asarray(got), np.asarray(want)
        exact += bool(np.array_equal(got, want))
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        worst = max(worst, rel)
    log(phase, bit_exact=f"{exact}/{len(ids)}", max_rel_diff=worst,
        bound=SERVICE_RTOL)
    if not worst <= SERVICE_RTOL:
        fail(f"{phase}: a result differs from its solo solve by {worst}")


# -- LM serving ---------------------------------------------------------------

def lm_phase(cfg, seed: int, *, requests: int, prompt: int,
             new: int) -> None:
    from repro.models.lm import Model
    from repro.runtime.server import Engine, Request, ServeConfig

    model = Model(cfg)
    params = model.init(jax.random.key(seed))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (requests, prompt), dtype=np.int32)
    out = {}
    for persistent in (True, False):
        phase = f"lm {cfg.name} {'persistent' if persistent else 'host_loop'}"
        eng = Engine(model, params, ServeConfig(
            max_batch=requests, persistent=persistent))
        for row in prompts:
            eng.submit(Request(prompt=row, max_new_tokens=new))
        with timed(phase):
            toks, stats = eng.run_batch()
        log(phase, tier=stats["tier"] or "host_loop",
            prefill_s=f"{stats['prefill_s']:.3f}",
            decode_s=f"{stats['decode_s']:.3f}",
            tok_per_s=f"{stats['tok_per_s']:.1f}")
        if toks.shape != (requests, new):
            fail(f"{phase}: generated {toks.shape}, not {(requests, new)}")
        out[persistent] = toks
    if not np.array_equal(out[True], out[False]):
        n = int((out[True] != out[False]).sum())
        fail(f"lm: persistent and host-loop tokens differ in {n} places")
    logits, cache = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, cache_seq=prompt + 1))(
        params, jnp.asarray(prompts))
    step, _ = jax.jit(model.decode_step)(params, cache, jnp.asarray(
        out[True][:, 0]))
    finite = bool(jnp.isfinite(logits).all() & jnp.isfinite(step).all())
    log("lm", tokens_identical=True, logits_finite=finite)
    if not finite:
        fail("lm: non-finite logits")


# -- four chips ---------------------------------------------------------------

def on_devices(phase: str, arr, n: int) -> None:
    devs = arr.sharding.device_set
    log(phase, devices=len(devs))
    if len(devs) != n:
        fail(f"{phase}: output sits on {len(devs)} devices, not {n}")


def four_chip_phases(chip, seed: int, *, side: int, cg_side: int,
                     iters: int) -> None:
    from repro.dist.mesh import make_mesh
    from repro.exec import CGProblem, Plan, StencilProblem, execute, plan
    from repro.kernels.common import get_spec
    from repro.kernels.spmv_ell import poisson2d_ell

    mesh = make_mesh((4,), ("data",))
    spec, steps = get_spec("2d5pt"), 20
    x = jax.random.uniform(jax.random.key(seed), (side, side), jnp.float32)
    prob = StencilProblem(x, spec, steps)
    phase = f"distributed stencil 2d5pt {side}x{side}"
    dplan = Plan(tier="distributed", fuse_steps=4, shard_axis="data")
    log(phase, tier=dplan.tier, fuse_steps=dplan.fuse_steps, chips=4)
    with timed(phase):
        y4 = jax.block_until_ready(execute(prob, dplan, mesh=mesh))
    on_devices(phase, y4, 4)
    one = plan(prob, chip=chip)
    with timed(f"{phase} one chip {one.tier}"):
        y1 = jax.block_until_ready(execute(prob, one))
    err = float(jnp.max(jnp.abs(jax.device_put(y4, y1.sharding) - y1)))
    log(phase, max_abs_diff_vs_one_chip=err, atol=STENCIL_ATOL)
    if not err <= STENCIL_ATOL:
        fail(f"{phase}: four chips and one differ by {err}")

    data, cols = poisson2d_ell(cg_side)
    b = np.random.default_rng(seed).standard_normal(cg_side ** 2)
    b = b.astype(np.float32)
    prob = CGProblem.from_ell(jnp.asarray(data), jnp.asarray(cols),
                              jnp.asarray(b), iters)
    phase = f"distributed CG fuse_reductions poisson2d {cg_side}^2"
    dplan = Plan(tier="distributed", fuse_reductions=True, shard_axis="data")
    log(phase, tier=dplan.tier, fuse_reductions=True, chips=4)
    with timed(phase):
        x4, _ = jax.block_until_ready(execute(prob, dplan, mesh=mesh))
    on_devices(phase, x4, 4)
    with timed(f"{phase} one chip device_loop"):
        x1, _ = jax.block_until_ready(
            execute(prob, Plan(tier="device_loop")))
    r4 = true_residual(data, cols, b, x4)
    r1 = true_residual(data, cols, b, x1)
    log(phase, true_rel_residual_4=r4, true_rel_residual_1=r1)
    # the pipelined recurrence is a different rounding path: both runs
    # must reach the same residual level after the same iterations
    if not (r4 <= 2 * r1 and r1 <= 2 * r4):
        fail(f"{phase}: residuals {r4} (four chips) and {r1} (one) differ")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's backend is "
              f"{devices[0].platform}); nothing run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs.registry import get_config
    from repro.core.compile_cache import enable_compile_cache
    from repro.core.hardware import attached_chip

    log("setup", compile_cache=enable_compile_cache())
    chip = attached_chip()
    log("setup", device_kind=devices[0].device_kind, chip=chip.name,
        devices=len(devices))
    if args.chips == 4:
        four_chip_phases(chip, args.seed, side=8192, cg_side=1024, iters=500)
    else:
        for name, side in (("2d5pt", 8192), ("2ds9pt", 8192),
                           ("2d5pt", 1024), ("3d7pt", 256)):
            stencil_phase(chip, name, side, steps=20, seed=args.seed)
        krylov_phase(chip, side=1024, seed=args.seed, max_iters=8000)
        service_phase(chip, args.seed, stencil_side=512, cg_side=128,
                      chunk=25)
        lm_phase(get_config("qwen2-0.5b"), args.seed, requests=8,
                 prompt=128, new=32)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
