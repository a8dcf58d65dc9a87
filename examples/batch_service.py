"""Serve a fleet of solver requests through batched persistent dispatches.

A production PERKS deployment rarely solves ONE problem: it serves many
users, each with a small stencil sweep or CG solve. This example builds a
mixed queue (two stencil families + CG right-hand sides against one
shared operator), lets ``SolverService`` pack it into shape-compatible
batches, and prints the per-request telemetry and the per-key Plans —
then compares batched against one-dispatch-per-user serving, and
finally serves a convergence-checked fleet through the
continuous-batching ``AsyncSolverService`` (DESIGN.md §9), where
converged lanes retire individually and late arrivals are admitted into
the freed lanes mid-solve.

Run:  PYTHONPATH=src python examples/batch_service.py [--users 24]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.exec import CGProblem, Plan, StencilProblem, execute_sequential
from repro.kernels.common import get_spec
from repro.runtime.solver_service import (
    AsyncConfig,
    AsyncSolverService,
    ServiceConfig,
    SolverService,
)
from repro.kernels.ops import load_dataset


def build_requests(users: int):
    """An interleaved multi-tenant queue: 2D stencils, 3D stencils, CG."""
    s2d, s3d = get_spec("2d5pt"), get_spec("3d7pt")
    data, cols = load_dataset("poisson_64")
    reqs = []
    for i in range(users):
        k = jax.random.key(i)
        if i % 3 == 0:
            x = jax.random.normal(k, (64, 64), jnp.float32)
            reqs.append(StencilProblem(x, s2d, 16))
        elif i % 3 == 1:
            x = jax.random.normal(k, (16, 16, 16), jnp.float32)
            reqs.append(StencilProblem(x, s3d, 16))
        else:
            b = jax.random.normal(k, (data.shape[0],), jnp.float32)
            reqs.append(CGProblem.from_ell(data, cols, b, 16))
    return reqs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--users", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    args = ap.parse_args(argv)

    reqs = build_requests(args.users)
    svc = SolverService(ServiceConfig(max_batch=args.max_batch))
    ids = [svc.submit(p) for p in reqs]
    print(f"queued {svc.pending()} requests "
          f"({len({p.batch_key() for p in reqs})} distinct batch keys)")

    results = svc.drain()
    stats = svc.stats()
    print(f"\nserved {stats['served']:.0f} requests in "
          f"{stats['batches']:.0f} batches "
          f"(mean batch {stats['mean_batch_size']:.1f}, "
          f"pad fraction {stats['pad_fraction']:.2f})")
    print(f"throughput {stats['instances_per_s']:.1f} instances/s, "
          f"mean latency {stats['mean_latency_s'] * 1e3:.1f} ms")

    print("\nper-key plans:")
    for key, p in svc.chosen_plans().items():
        print(f"  {p.problem:32s} tier={p.tier:12s} fuse={p.fuse_steps} "
              f"B={p.batch}")

    one = results[ids[0]]
    print(f"\nrequest 0: queued {one.queued_s * 1e3:.1f} ms, rode a "
          f"{one.batch_size}-request batch padded to {one.padded_to}")

    # the naive service: one dispatch sequence per user, same tier
    t0 = time.perf_counter()
    for p in reqs:
        jax.block_until_ready(
            execute_sequential([p], Plan(tier="device_loop")))
    seq_s = time.perf_counter() - t0
    print(f"\nsequential serving of the same queue: {seq_s:.2f} s "
          f"({args.users / seq_s:.1f} instances/s) — batched is "
          f"{seq_s / max(stats['exec_s_total'], 1e-9):.1f}x on dispatch "
          f"wall time")

    # -- continuous batching: churn membership, keep the program hot ----
    data, cols = load_dataset("poisson_64")
    eng = AsyncSolverService(AsyncConfig(max_batch=4, chunk_steps=25))
    fleet = [CGProblem.from_ell(
        data, cols,
        jax.random.normal(jax.random.key(100 + i), (data.shape[0],),
                          jnp.float32),
        400, tol=1e-8) for i in range(4)]
    for p in fleet:
        eng.submit(p)
    eng.step()                               # first barrier of the group
    late = CGProblem.from_ell(
        data, cols,
        jax.random.normal(jax.random.key(999), (data.shape[0],),
                          jnp.float32),
        400, tol=1e-8)
    eng.submit(late)                         # lands in a freed lane
    out = eng.run_until_idle()
    es = eng.stats()
    print(f"\nasync engine: served {es['served']:.0f} tol-checked solves "
          f"in {es['barriers']:.0f} barriers — "
          f"{es['retired_early']:.0f} lanes retired early, "
          f"{es['admitted_mid_solve']:.0f} admitted mid-solve")
    steps = sorted(r.steps for r in out.values())
    print(f"per-lane stop steps {steps} (a static batch would run every "
          f"lane to {max(steps)}); p99 latency "
          f"{es['p99_latency_s'] * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
