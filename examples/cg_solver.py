"""Conjugate gradient under PERKS: solve one sparse SPD system three ways.

    PYTHONPATH=src python examples/cg_solver.py
    PYTHONPATH=src python examples/cg_solver.py --dataset graph_powerlaw_8k
    PYTHONPATH=src python examples/cg_solver.py --list

``--dataset`` accepts any name from the SuiteSparse-proxy registry
(``repro.sparse.generate``) or the synthetic ``poisson_*``/``banded_*``
suite; the solve is
preceded by the cache planner's policy choice and the ELL vs SELL-C-σ
padding report for the chosen matrix.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.cache_policy import cg_arrays_for
from repro.core.hardware import TPU_V5E
from repro.exec import CGProblem, Plan, execute, fused_block_rows
from repro.exec.planner import cg_policy_from_arrays
from repro.kernels.ops import SellOperator
from repro.sparse import DATASETS, REGISTRY, choose_format, load_matrix
from repro.sparse.generate import PROXY_ONCHIP_BYTES


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="poisson_128",
                    help="registry or synthetic dataset name")
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--list", action="store_true",
                    help="list available datasets and exit")
    args = ap.parse_args()
    if args.list:
        for name in DATASETS:
            spec = REGISTRY.get(name)
            note = (f"  [{spec.structure}] {spec.note}" if spec
                    else "  [synthetic]")
            print(f"{name:20s}{note}")
        return

    csr = load_matrix(args.dataset)
    n = csr.shape[0]
    iters = args.iters

    fmt, reports = choose_format(csr, c=32, sigma=256)
    arrays = cg_arrays_for(csr)
    plan = cg_policy_from_arrays(arrays, int(TPU_V5E.onchip_bytes * 0.9))
    regime = cg_policy_from_arrays(arrays, PROXY_ONCHIP_BYTES)["policy"]
    print(f"dataset {args.dataset}: n={n}, nnz={csr.nnz}")
    print(f"  planner        : policy={plan['policy']} "
          f"(vectors {plan['vector_fraction']:.0%}, "
          f"matrix {plan['matrix_fraction']:.0%} resident); "
          f"proxy-capacity regime={regime}; format={fmt}")
    for name, rep in reports.items():
        print(f"  padding [{name:4s}] : fill={rep.fill_ratio:5.1%}  "
              f"bytes={rep.bytes:>11,}  ({rep.bytes_vs_csr:.2f}x CSR)")

    ell = csr.to_ell()
    data, cols = jnp.asarray(ell.data), jnp.asarray(ell.cols)
    b = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
    bb = float(jnp.vdot(b, b))

    # one problem, three plans — the unified executor (DESIGN.md §7).
    # tol only on the device-loop problem: the chunked loop is the tier
    # with host-sync points to evaluate the convergence check at.
    problem = CGProblem.from_ell(data, cols, b, iters, matrix=csr)
    problem_tol = CGProblem.from_ell(data, cols, b, iters, matrix=csr,
                                     tol=1e-12)

    t0 = time.perf_counter()
    x_h, rr_h = execute(problem, Plan(tier="host_loop"))
    jax.block_until_ready(x_h)
    t_h = time.perf_counter() - t0

    t0 = time.perf_counter()
    x_d, rr_d = execute(problem_tol, Plan(tier="device_loop", sync_every=20))
    jax.block_until_ready(x_d)
    t_d = time.perf_counter() - t0

    x_f, rr_f = execute(problem, Plan(
        tier="resident",
        policy=plan["policy"] if plan["policy"] in ("VEC", "MIX") else "MIX",
        block_rows=fused_block_rows(n)))

    print(f"CG {args.dataset} (n={n}), {iters} iters (|b|^2 = {bb:.1f})")
    print(f"  host loop      : {t_h * 1e3:7.1f} ms, "
          f"rr/bb = {float(rr_h) / bb:.2e}")
    print(f"  PERKS fused    : {t_d * 1e3:7.1f} ms "
          f"({t_h / t_d:.2f}x), rr/bb = {float(rr_d) / bb:.2e}")
    print(f"  PERKS kernel   : rr/bb = {float(rr_f) / bb:.2e} "
          f"(whole loop in one Pallas kernel, vectors VMEM-resident)")
    if fmt == "sell":
        op = SellOperator.from_matrix(csr.to_sell(c=32, sigma=256))
        sell_problem = CGProblem.from_matvec(op.matvec, b, iters, matrix=csr)
        t0 = time.perf_counter()
        x_s, rr_s = execute(sell_problem, Plan(tier="device_loop"))
        jax.block_until_ready(x_s)
        t_s = time.perf_counter() - t0
        print(f"  SELL-C-σ loop  : {t_s * 1e3:7.1f} ms, "
              f"rr/bb = {float(rr_s) / bb:.2e} "
              f"(per-slice K kernel on the planner-chosen format)")


if __name__ == "__main__":
    main()
