"""``execute(problem, plan)`` — the single dispatch path for every tier —
and ``autotune``, which measures the planner's top candidates and returns
the empirical winner with its timing table.

The executor owns only *orchestration*: the loop combinators
(``core.perks``) for the host/device tiers and the problem's own tier
hooks for resident/distributed. All workload specifics live in the
Problem adapters, all decisions in the Plan (DESIGN.md §7).
"""
from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Optional, Sequence

import jax

from repro import obs
from repro.core import perks
from repro.exec.plan import Plan
from repro.exec.problem import Problem
from repro.exec import planner as _planner


def _record_plan_metrics(plan: Plan) -> None:
    """Executor-level counters the service layer can't see (DESIGN.md
    §11): fused steps per HBM pass, bytes resident vs streamed per
    CacheDecision, collective rounds, and the barriers of the resident and
    distributed tiers, which never stop early and so pay the plan's count.
    The loop tiers count the steps, host syncs and barriers that ran
    themselves (``core.perks``)."""
    mx = obs.get_metrics()
    mx.counter("executor_executions_total", tier=plan.tier).inc()
    if plan.tier in ("resident", "distributed"):
        mx.counter("executor_barriers_total", tier=plan.tier).inc(
            plan.barriers)
    mx.gauge("executor_fused_steps_per_pass", tier=plan.tier).set(
        plan.fuse_steps)
    if plan.cache:
        streamed = sum(d.total_bytes - d.cached_bytes for d in plan.cache)
        mx.counter("executor_cache_decisions_total").inc(len(plan.cache))
        mx.counter("executor_bytes_cached_total").inc(plan.cached_bytes)
        mx.counter("executor_bytes_streamed_total").inc(streamed)
    if plan.tier == "distributed":
        mx.counter("executor_collective_rounds_total").inc(plan.barriers)


def execute(problem: Problem, plan: Plan, *, mesh=None):
    """Run ``problem`` under ``plan``; returns the problem's final result.

    Every tier runs the problem's one step function or its kernels, so
    the tiers agree: bit for bit between the loop tiers, to <= 2 ulp where
    ``fuse_steps > 1`` changes window shapes (DESIGN.md §4). The ambient
    observability context (``repro.obs``) sees every call: executor
    counters and the ``repro.dispatch`` span around the run (with the
    loop tiers' compile/chunk/barrier spans inside it) always, cache events and in-memory records when a real tracer is
    installed, and a predicted-vs-measured row in the drift ledger when
    one is active (the ledger blocks on the result to time it — values
    are unchanged, only laziness).
    """
    if plan.n_steps and plan.n_steps != problem.n_steps:
        raise ValueError(
            f"plan.n_steps={plan.n_steps} != problem.n_steps="
            f"{problem.n_steps}; plans are per-problem-instance")
    if plan.batch != problem.batch:
        raise ValueError(
            f"plan.batch={plan.batch} != problem.batch={problem.batch}; "
            f"a batched plan must run the BatchedProblem it was made for "
            f"(repro.exec.batch)")
    if not problem.supports(plan.tier):
        raise NotImplementedError(
            f"{type(problem).__name__} does not support tier {plan.tier!r}")
    if plan.precision != "uniform":
        # the Plan owns the decision; the problem owns the mechanism
        # (swapping its reductions — exec.precision.dot_for). Problems
        # that don't implement the precision raise here, before any work.
        problem = problem.with_precision(plan.precision)
    on_sync = problem.on_sync()
    if on_sync is not None and not honors_on_sync(plan, problem.n_steps):
        # The problem declared a convergence check (e.g. CGProblem.tol)
        # but this plan has no host-sync points to evaluate it at — the
        # run completes all n_steps. plan() sets sync_every on loop-tier
        # CG candidates automatically; hand-built plans must opt in.
        warnings.warn(
            f"{problem.name} declares a convergence check but the "
            f"{plan.tier} plan has no host-sync points (sync_every="
            f"{plan.sync_every}); running all {problem.n_steps} steps",
            RuntimeWarning, stacklevel=2)
    if plan.tier == "distributed" and mesh is None:
        raise ValueError("distributed plan needs mesh=")
    tr = obs.get_tracer()
    ledger = obs.get_ledger()
    _record_plan_metrics(plan)
    track = f"tier:{plan.tier}"
    if tr.enabled:
        for d in plan.cache:
            tr.event(f"cache:{d.name}", cat="cache", track=track,
                     problem=problem.name, cached_bytes=d.cached_bytes,
                     total_bytes=d.total_bytes, fraction=d.fraction)
    t0 = time.perf_counter() if ledger is not None else 0.0
    result = _dispatch(problem, plan, mesh, on_sync, tr, track)
    if ledger is not None:
        result = jax.block_until_ready(result)
        ledger.record(problem, plan, time.perf_counter() - t0)
    return result


def _dispatch(problem: Problem, plan: Plan, mesh, on_sync, tracer, track):
    """The tier dispatch proper, under the ``repro.dispatch`` span
    (validation, counters and the ledger live in ``execute``)."""
    # a Krylov problem's name holds a content fingerprint, read from the
    # device: only a recording tracer pays for it
    label = problem.name if tracer.enabled else problem.kind
    spmv = (problem.spmv_format if plan.tier in ("host_loop", "device_loop")
            else None)
    # a distributed call's split and halo traffic, from the plan as the
    # barriers are (``Problem.halo_split``)
    split = (problem.halo_split(plan, mesh)
             if plan.tier == "distributed" and mesh is not None else None)
    if split is not None:
        obs.get_metrics().counter("executor_halo_bytes_total",
                                  tier=plan.tier).inc(split.pop("halo_bytes"))
    with tracer.span(f"execute:{label}", cat="dispatch", track=track,
                     tier=plan.tier, fuse_steps=plan.fuse_steps,
                     batch=plan.batch, n_steps=problem.n_steps,
                     barriers=plan.barriers,
                     **({"spmv": spmv} if spmv else {}), **(split or {})):
        if plan.tier == "distributed":
            if mesh is None:
                raise ValueError("distributed plan needs mesh=")
            return problem.run_distributed(plan, mesh)
        if plan.tier == "resident":
            return problem.run_resident(plan)
        execution = (perks.Execution.HOST_LOOP if plan.tier == "host_loop"
                     else perks.Execution.DEVICE_LOOP)
        cfg = perks.PerksConfig(execution=execution,
                                sync_every=plan.sync_every,
                                fuse_steps=plan.fuse_steps)
        metrics = obs.get_metrics()
        # the runner is cached by the step function; the operator comes in
        # as an argument, so a later solve over it builds nothing
        fn, operands = problem.step()
        runner = perks.persistent(fn, problem.n_steps, cfg, on_sync=on_sync,
                                  metrics=metrics, operands=(operands,))
        if spmv:
            metrics.counter("executor_spmv_total", format=spmv).inc()
        return problem.finalize(runner(problem.initial_state()))


def honors_on_sync(plan: Plan, n_steps: int) -> bool:
    """Whether this plan's execution path ever calls the problem's
    ``on_sync`` callback (see ``core.perks.persistent``): HOST_LOOP is
    back on the host after EVERY dispatch, so it always honors the check
    (each step when fuse_steps == 1, each fused chunk otherwise);
    DEVICE_LOOP only when sync_every < n; the resident kernels and the
    distributed programs never return to the host mid-run."""
    if plan.tier == "host_loop":
        return True
    if plan.tier == "device_loop":
        return plan.sync_every is not None and plan.sync_every < n_steps
    return False


@dataclasses.dataclass(frozen=True)
class TimingRow:
    """One autotune measurement: the plan, its planner prediction, and the
    measured wall-clock seconds (median over ``iters`` timed calls)."""

    plan: Plan
    predicted_s: Optional[float]
    measured_s: float

    @property
    def prediction_ratio(self) -> Optional[float]:
        """measured / predicted — how far off the model was (CPU interpret
        mode inflates this; the *ranking* is what transfers). None only
        when there IS no prediction; a predicted 0.0 is a real (if absurd)
        projection and reports ``inf`` rather than masquerading as
        "no prediction"."""
        if self.predicted_s is None:
            return None
        if self.predicted_s == 0.0:
            return math.inf if self.measured_s > 0.0 else 1.0
        return self.measured_s / self.predicted_s


@dataclasses.dataclass(frozen=True)
class AutotuneResult:
    best: Plan
    table: tuple[TimingRow, ...]   # planner order (rank 0 = predicted best)

    def row_for(self, plan: Plan) -> TimingRow:
        for r in self.table:
            if r.plan == plan:
                return r
        raise KeyError("plan not in autotune table")


def _time_once(fn, warmup: int, iters: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def autotune(problem: Problem, candidates: Optional[Sequence[Plan]] = None,
             *, chip=None, mesh=None, top_k: int = 4, warmup: int = 1,
             iters: int = 3, ledger=None, **plan_kw) -> AutotuneResult:
    """Measure the top-``top_k`` planner candidates and return the winner.

    ``candidates`` defaults to ``plan_candidates(problem, ...)``
    (distributed plans are dropped unless ``mesh`` is given). The result's
    ``table`` keeps the planner's predicted order so callers can report
    predicted-vs-measured per candidate (the ``exec_plan_*`` benchmark
    rows); ``best`` is the measured winner.

    ``ledger`` (default: the ambient ``repro.obs.get_ledger()``) is the
    persisted drift ledger: a candidate this ledger has already timed on
    this chip/jax version is NOT re-measured — its stored ``measured_s``
    fills the row (``ledger.hits`` counts the skips) — and every fresh
    measurement plus the empirical winner is written back, so the next
    process starts from this one's evidence (ROADMAP item 5).
    """
    if candidates is None:
        kw = dict(plan_kw)
        if chip is not None:
            kw["chip"] = chip
        candidates = _planner.plan_candidates(problem, mesh=mesh, **kw)
    if ledger is None:
        ledger = obs.get_ledger()
    tr = obs.get_tracer()
    runnable = [p for p in candidates
                if p.tier != "distributed" or mesh is not None]
    if not runnable:
        raise ValueError("no runnable candidates for this problem/host")
    rows = []
    for p in runnable[:max(1, top_k)]:
        rec = ledger.lookup(problem, p) if ledger is not None else None
        if rec is not None:
            measured = rec.measured_s
        else:
            measured = _time_once(lambda: execute(problem, p, mesh=mesh),
                                  warmup, iters)
            if ledger is not None:
                ledger.record(problem, p, measured)
        row = TimingRow(p, p.predicted_s, measured)
        if tr.enabled:
            tr.event("autotune_measure", cat="measure", track="autotune",
                     problem=problem.name, plan=obs.plan_signature(p),
                     predicted_s=p.predicted_s, measured_s=measured,
                     from_ledger=rec is not None)
        rows.append(row)
    best = min(rows, key=lambda r: r.measured_s).plan
    if ledger is not None:
        ledger.set_best(problem, best)
    return AutotuneResult(best=best, table=tuple(rows))
