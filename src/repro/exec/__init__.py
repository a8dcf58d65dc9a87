"""repro.exec — the unified PERKS executor (DESIGN.md §7).

One solver-agnostic pipeline behind every iterative workload:

    Problem  ->  plan()/plan_candidates()  ->  execute()  ->  autotune()

* :class:`Problem` (``problem.py``) — what a workload must expose: step
  function, initial state, cacheable arrays, halo/partition spec, oracle.
  Adapters for the paper's workloads live in ``adapters.py``
  (:class:`StencilProblem`, :class:`CGProblem`).
* :class:`Plan` (``plan.py``) — an immutable record of *how* to run
  (tier, fuse depth, cache assignment, shard axis) with a JSON
  round-trip, so chosen plans are loggable artifacts.
* :func:`plan` (``planner.py``) — the one planner; ranks candidates with
  the paper's performance model.
* :func:`execute` / :func:`autotune` (``executor.py``) — the single
  dispatch path over all tiers, and measured top-k plan selection.
* :class:`BatchedProblem` (``batch.py``, DESIGN.md §8) — B instances
  behind one persistent dispatch per tier; ``plan(problem, batch=B)``
  re-prices candidates under the B-scaled working set, and
  ``runtime/solver_service.py`` serves heterogeneous request queues
  through it.

* The Krylov family (``krylov.py``, DESIGN.md §10) — BiCGStab, restarted
  GMRES(m) and s-step CG as Problem adapters, with mixed precision as a
  Plan dimension (``precision.py``): every tier, the batched dispatch and
  the async service serve them with zero solver-specific code.
* The ML workloads (``ml.py``, DESIGN.md §13) —
  :class:`DecodeAttentionProblem` (token-by-token LM decode; KV cache as
  the cacheable operand, EOS as the batchable convergence contract) and
  :class:`SSMScanProblem` (the Mamba2 SSD scan; chunk index as the time
  axis, state ``h`` VMEM-resident on the resident tier), so the serving
  engine (``runtime/server.py``) decodes through ``plan()``/``execute()``.
"""
from repro.exec.adapters import (
    CGProblem,
    StencilProblem,
    fused_block_rows,
    fusion_schedule,
    make_distributed_step,
    operator_fingerprint,
)
from repro.exec.batch import (
    BatchedProblem,
    autotune_batch_sweep,
    execute_sequential,
)
from repro.exec.executor import AutotuneResult, TimingRow, autotune, execute
from repro.exec.krylov import (
    BiCGStabProblem,
    GMRESProblem,
    cg_sstep_distributed,
    cg_sstep_run,
)
from repro.exec.ml import DecodeAttentionProblem, SSMScanProblem
from repro.exec.plan import TIERS, CacheDecision, Plan
from repro.exec.planner import plan, plan_candidates
from repro.exec.precision import (
    PRECISIONS,
    compensated_vdot,
    dot_for,
    solve_refined,
)
from repro.exec.problem import HaloSpec, Problem, operand_fingerprint

__all__ = [
    "AutotuneResult",
    "BatchedProblem",
    "BiCGStabProblem",
    "CGProblem",
    "CacheDecision",
    "DecodeAttentionProblem",
    "GMRESProblem",
    "HaloSpec",
    "PRECISIONS",
    "Plan",
    "Problem",
    "SSMScanProblem",
    "StencilProblem",
    "TIERS",
    "TimingRow",
    "autotune",
    "autotune_batch_sweep",
    "cg_sstep_distributed",
    "cg_sstep_run",
    "compensated_vdot",
    "dot_for",
    "execute",
    "execute_sequential",
    "fused_block_rows",
    "fusion_schedule",
    "make_distributed_step",
    "operand_fingerprint",
    "operator_fingerprint",
    "plan",
    "plan_candidates",
    "solve_refined",
]
